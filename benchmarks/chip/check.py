"""The comparison that decides ``correct``: program against plain reference.

Training compares these numbers, each against its limit in
``limits/<cell>.json``:

* ``loss_gap``: the largest relative gap of the first steps' losses, and
  ``loss1_gap`` the relative gap of the first step's loss alone;
* ``grad_gap``: per table, the gap between the program's first-gradient norm
  (read from its Adagrad accumulator after the first update) and the
  reference's, over the larger of that table's and the median table's
  reference norm; the worst table counts;
* ``change_gap``: the same for the norm of each table's change over the
  first steps. A table whose reference gradient is under a thousandth of the
  median table's moves by round-off alone under Adagrad and is left out.

The batches the program's sampler fed to those steps are checked on their
own, since the reference replays them:

* ``batch_faults``: how many positives are not triplets of the training
  split (head and tail swapped counts here), ids out of range, and batches
  whose negatives do not have the configured (2, b / g, k) shape;
* ``neg_spread``: the negatives drawn from the whole entity range, cut into
  ``NEG_BINS`` equal bins: the largest relative gap of a bin's count from
  its uniform share.
"""

from __future__ import annotations

import math

import numpy as np


def _rel_gap(p: float, r: float, scale: float) -> float:
    if not (math.isfinite(p) and math.isfinite(r)):
        return math.inf
    return abs(p - r) / scale if scale > 0 else (0.0 if p == r else math.inf)


def train_numbers(prog: dict, ref: dict) -> dict:
    """prog and ref: {"loss": [...], "grad_norm": {table: x}, "change_norm": {...}}."""
    gaps = [_rel_gap(p, r, abs(r)) for p, r in zip(prog["loss"], ref["loss"])]
    g = ref["grad_norm"]
    g_med = float(np.median(list(g.values())))
    grad_gap = max(_rel_gap(prog["grad_norm"][k], g[k], max(g[k], g_med)) for k in g)
    moved = [k for k in g if g[k] >= 1e-3 * g_med]
    c = ref["change_norm"]
    c_med = float(np.median([c[k] for k in moved]))
    change_gap = max(_rel_gap(prog["change_norm"][k], c[k], max(c[k], c_med))
                     for k in moved)
    return {"loss_gap": max(gaps), "loss1_gap": gaps[0], "grad_gap": grad_gap,
            "change_gap": change_gap}


NEG_BINS = 8


def batch_numbers(batches: list, train: np.ndarray, kge: dict) -> dict:
    """Check the sampler's batches against the training split and the
    configuration. Of each group's ``k`` negatives the first ``k - n_deg``
    are uniform over all entities and the last ``n_deg = round(k *
    neg_deg_ratio)`` are entities of the batch (the in-batch, degree-based
    share); only the uniform ones are binned."""
    n_ent, n_rel, k = kge["n_entities"], kge["n_relations"], kge["neg_sample_size"]
    n_uni = k - int(round(k * kge.get("neg_deg_ratio", 0.0)))
    shape = (2, kge["batch_size"] // kge["neg_group_size"], k)

    def key(h, r, t):
        return (h.astype(np.int64) * n_rel + r) * n_ent + t

    known = np.sort(key(train[:, 0], train[:, 1], train[:, 2]))
    faults, uniform = 0, []
    for b in batches:
        h, r, t, neg = (np.asarray(b[c]) for c in ("h", "r", "t", "neg"))
        bad = ((h < 0) | (h >= n_ent) | (t < 0) | (t >= n_ent)
               | (r < 0) | (r >= n_rel))
        faults += int(bad.sum())
        q = key(h, r, t)
        at = np.minimum(np.searchsorted(known, q), known.size - 1)
        faults += int((known[at] != q).sum())
        if h.shape[0] != kge["batch_size"] or neg.shape != shape:
            faults += 1
            continue
        faults += int(((neg < 0) | (neg >= n_ent)).sum())
        faults += int((~np.isin(neg[..., n_uni:], np.concatenate([h, t]))).sum())
        uniform.append(neg[..., :n_uni].ravel())
    neg = np.concatenate(uniform) if uniform else np.zeros(0, np.int64)
    counts = np.bincount(np.clip(neg, 0, n_ent - 1) * NEG_BINS // n_ent,
                         minlength=NEG_BINS)
    share = counts / max(1, neg.size) * NEG_BINS  # 1 in every bin when uniform
    return {"batch_faults": float(faults),
            "neg_spread": float(np.abs(share - 1.0).max())}


def verdict(numbers: dict, limits: dict) -> bool:
    """True where every number is finite and within its limit."""
    return all(math.isfinite(numbers[k]) and numbers[k] <= limits[k] for k in limits)
