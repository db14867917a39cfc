"""A plain float32 reference of one KGE train step, written from the
definitions and sharing no code with the program.

Scores are computed for every (triplet, candidate) pair directly, with no
joint decomposition; gradients come from ``jax.grad`` of that straight-line
loss with respect to the rows each triplet reads; the sparse Adagrad update
is numpy. Matmuls run at ``precision=HIGHEST``.

Conventions it shares with the program, because they define the tables:

* ComplEx and RotatE interleave real and imaginary parts (``0::2`` real,
  ``1::2`` imaginary).
* RotatE's relation phase is ``r[0::2] / emb_scale * pi`` with
  ``emb_scale = (gamma + 2) / dim``.
* TransR and RESCAL keep one ``dim x rel_dim`` projection per relation,
  flattened row-major; RESCAL needs ``rel_dim == dim``.
* Negatives: ``neg`` is ``(2, ng, k)`` for joint negatives, the ``ng``
  groups splitting the batch in order, or ``(2, b, k)`` for naive ones
  (one triplet a group). Mode 0 corrupts the tail, mode 1 the head.
* Losses: ``logistic``, ``ranking`` (margin ``gamma``) and ``self_adv``
  (softmax weights of the negative scores, held constant), over every
  positive once per mode.
* Sparse Adagrad: sum the gradients of duplicate rows, then
  ``gsq += g**2`` and ``row -= lr * g / (sqrt(gsq) + 1e-10)``.
* With ``deferred=True`` (T5) the entity update of a step lands at the
  start of the next step, before its rows are read; relation and
  projection updates land at once. ``flush()`` lands the last one.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-10


def _norm(x):
    """Euclidean norm over the last axis (the program's 1e-12 floor)."""
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=-1) + 1e-12)


def _project(x, m):
    """x (..., d) times each pair's projection m (..., d, R) -> (..., R)."""
    return jnp.matmul(x[..., None, :], m, precision=HIGHEST)[..., 0, :]


def score(model: str, h, r, t, m, gamma: float, emb_scale: float):
    """Scores of (h, r, t) with broadcasting leading axes; h, t (..., d),
    r (..., rel_dim), m (..., d, rel_dim) or None."""
    if model == "transe_l1":
        return gamma - jnp.sum(jnp.abs(h + r - t), axis=-1)
    if model == "transe_l2":
        return gamma - _norm(h + r - t)
    if model == "distmult":
        return jnp.sum(h * r * t, axis=-1)
    if model == "complex":
        hr, hi = h[..., 0::2], h[..., 1::2]
        rr, ri = r[..., 0::2], r[..., 1::2]
        tr, ti = t[..., 0::2], t[..., 1::2]
        # Re(<h, r, conj(t)>)
        return jnp.sum(hr * rr * tr + hi * rr * ti + hr * ri * ti - hi * ri * tr,
                       axis=-1)
    if model == "rotate":
        phase = r[..., 0::2] / emb_scale * jnp.pi
        c, s = jnp.cos(phase), jnp.sin(phase)
        hr, hi = h[..., 0::2], h[..., 1::2]
        tr, ti = t[..., 0::2], t[..., 1::2]
        dr = hr * c - hi * s - tr
        di = hr * s + hi * c - ti
        return gamma - jnp.sqrt(jnp.sum(dr * dr + di * di, axis=-1) + 1e-12)
    if model == "transr":
        return gamma - _norm(_project(h, m) + r - _project(t, m))
    if model == "rescal":
        return jnp.sum(_project(h, m) * t, axis=-1)
    raise ValueError(model)


def loss_of(kind: str, pos, neg, gamma: float):
    """pos (b,), neg (2, b, k): each positive meets its negatives per mode."""
    p = jnp.concatenate([pos, pos])  # (2b,)
    n = neg.reshape(p.shape[0], -1)  # (2b, k)
    if kind == "logistic":
        return jnp.mean(jax.nn.softplus(-p)) + jnp.mean(jax.nn.softplus(n))
    if kind == "ranking":
        return jnp.mean(jnp.maximum(0.0, gamma - p[:, None] + n))
    if kind == "self_adv":
        w = jax.nn.softmax(jax.lax.stop_gradient(n), axis=-1)
        return (jnp.mean(jax.nn.softplus(-p))
                + jnp.mean(jnp.sum(w * jax.nn.softplus(n), axis=-1)))
    raise ValueError(kind)


class StepReference:
    """The reference trainer: ``step(h, r, t, neg) -> loss`` on host arrays.

    ``tables`` holds float32 ``entity``, ``rel`` and, for TransR and RESCAL,
    ``proj``; every table gets a zero ``gsq`` of its shape.
    """

    def __init__(self, model: str, loss: str, *, gamma: float, lr: float,
                 rel_dim: int, tables: Dict[str, np.ndarray],
                 deferred: bool = False):
        self.model, self.loss, self.gamma, self.lr = model, loss, gamma, lr
        self.rel_dim = rel_dim
        self.tables = {k: np.array(v, np.float32) for k, v in tables.items()}
        self.gsq = {k: np.zeros_like(v) for k, v in self.tables.items()}
        self.emb_scale = (gamma + 2.0) / self.tables["entity"].shape[1]
        self.deferred = deferred
        self.pending: Optional[Tuple[np.ndarray, np.ndarray]] = None
        # smallest |g| / max |g| over components a table updates for the
        # first time: that update is lr * sign(g), so a component within
        # rounding of zero would make the comparison a coin toss
        self.min_first_touch = np.inf
        self._grad = jax.jit(jax.value_and_grad(self._loss, argnums=(0, 1, 2, 3, 4)))

    # ---- the loss over the rows one batch reads
    def _loss(self, h, t, negs, r, m):
        """h, t (b, d); negs (2, ng, k, d); r (b, rel_dim); m (b, d, R)|None."""
        b, ng = h.shape[0], negs.shape[1]
        per_triplet = negs[:, jnp.arange(b) // (b // ng)]  # (2, b, k, d)
        mm = None if m is None else m[:, None]
        args = (self.gamma, self.emb_scale)
        pos = score(self.model, h, r, t, m, *args)
        tail = score(self.model, h[:, None], r[:, None], per_triplet[0], mm, *args)
        head = score(self.model, per_triplet[1], r[:, None], t[:, None], mm, *args)
        return loss_of(self.loss, pos, jnp.stack([tail, head]), self.gamma)

    # ---- sparse Adagrad
    def _adagrad(self, name: str, ids: np.ndarray, rows: np.ndarray) -> None:
        table, gsq = self.tables[name], self.gsq[name]
        g = np.zeros_like(table)
        np.add.at(g, ids, rows)  # duplicate rows summed
        touched = np.unique(ids)
        gt = g[touched]
        first = (gsq[touched] == 0) & (gt != 0)
        if first.any():
            self.min_first_touch = min(self.min_first_touch, float(
                np.min(np.abs(gt[first])) / np.max(np.abs(gt))))
        gsq[touched] += gt * gt
        table[touched] -= np.float32(self.lr) * gt / (np.sqrt(gsq[touched])
                                                      + np.float32(EPS))

    def step(self, h, r, t, neg) -> float:
        if self.pending is not None:
            self._adagrad("entity", *self.pending)
            self.pending = None
        ent, rel = self.tables["entity"], self.tables["rel"]
        proj = self.tables.get("proj")
        d = ent.shape[1]
        m = None if proj is None else proj[r].reshape(len(r), d, self.rel_dim)
        loss, (gh, gt, gn, gr, gm) = self._grad(ent[h], ent[t], ent[neg], rel[r], m)
        ent_ids = np.concatenate([h, t, neg.reshape(-1)])
        ent_rows = np.concatenate([np.asarray(gh), np.asarray(gt),
                                   np.asarray(gn).reshape(-1, d)])
        self._adagrad("rel", r, np.asarray(gr))
        if proj is not None:
            self._adagrad("proj", r, np.asarray(gm).reshape(len(r), -1))
        if self.deferred:
            self.pending = (ent_ids, ent_rows)
        else:
            self._adagrad("entity", ent_ids, ent_rows)
        return float(loss)

    def flush(self) -> None:
        if self.pending is not None:
            self._adagrad("entity", *self.pending)
            self.pending = None
