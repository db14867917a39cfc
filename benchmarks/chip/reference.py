"""Plain reference of what the cells compute, independent of the program.

Straightforward ``jax.numpy`` at float32 with every matrix product at
``HIGHEST`` precision: the score functions written from their definitions
(TransE-L2 as the distance itself, not its expansion), the loss, autodiff
gradients against the whole tables, and dense Adagrad (untouched rows get a
zero gradient and so do not move). Weights are drawn from the seed by the
initialisation the configuration states: entity and relation rows uniform in
(-s, s), s = (gamma + 2) / dim, from three splits of the seed's key. Called
with ``dtype=jnp.bfloat16`` it is the control: the same mathematics one
precision below the configuration's float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("tail", "head")
ADAGRAD_EPS = 1e-10


def init_tables(kge: dict, seed: int, dtype=jnp.float32) -> dict:
    s = (kge["gamma"] + 2.0) / kge["dim"]
    k1, k2, _ = jax.random.split(jax.random.key(seed), 3)
    ent = jax.random.uniform(k1, (kge["n_entities"], kge["dim"]), jnp.float32, -s, s)
    rel = jax.random.uniform(k2, (kge["n_relations"], kge["rel_dim"] or kge["dim"]),
                             jnp.float32, -s, s)
    return {"entity": ent.astype(dtype), "rel": rel.astype(dtype)}


def positive_score(model, h, r, t, gamma):
    if model == "transe_l2":
        return gamma - jnp.sqrt(jnp.sum(jnp.square(h + r - t), axis=-1))
    if model == "distmult":
        return jnp.sum(h * r * t, axis=-1)
    raise ValueError(f"no reference for score function {model!r}")


def candidate_scores(model, h, r, t, cands, gamma, corrupt):
    """(q, C) scores of replacing the tail (or head) of q triplets by each of
    C candidate entities."""
    if model == "transe_l2":
        if corrupt == "tail":
            diff = (h + r)[:, None, :] - cands[None]
        else:
            diff = cands[None] + (r - t)[:, None, :]
        return gamma - jnp.sqrt(jnp.sum(jnp.square(diff), axis=-1))
    if model == "distmult":
        q = h * r if corrupt == "tail" else r * t
        return jnp.einsum("qd,cd->qc", q, cands, precision=HIGHEST)
    raise ValueError(f"no reference for score function {model!r}")


def loss(kge: dict, params: dict, batch: dict):
    """Mean loss of one joint-negative batch: h, r, t (b,), neg (2, ng, k)."""
    model, gamma = kge["model"], kge["gamma"]
    ent, rel = params["entity"], params["rel"]
    h, r, t = ent[batch["h"]], rel[batch["r"]], ent[batch["t"]]
    pos = positive_score(model, h, r, t, gamma)
    n_groups = batch["neg"].shape[1]
    gsz = h.shape[0] // n_groups
    negs = []
    for m, corrupt in enumerate(MODES):
        for g in range(n_groups):
            sl = slice(g * gsz, (g + 1) * gsz)
            negs.append(candidate_scores(model, h[sl], r[sl], t[sl],
                                         ent[batch["neg"][m, g]], gamma, corrupt))
    neg = jnp.concatenate(negs)  # (2b, k)
    lp = jnp.mean(jax.nn.softplus(-pos))
    if kge["loss"] == "self_adv":
        w = jax.nn.softmax(jax.lax.stop_gradient(neg), axis=-1)
        return lp + jnp.mean(jnp.sum(w * jax.nn.softplus(neg), axis=-1))
    if kge["loss"] == "logistic":
        return lp + jnp.mean(jax.nn.softplus(neg))
    raise ValueError(f"no reference for loss {kge['loss']!r}")


@functools.partial(jax.jit, static_argnums=0)
def _step(kge_items, params, gsq, batch):
    kge = dict(kge_items)
    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(functools.partial(loss, kge))(params, batch)
        new_gsq = {k: gsq[k] + jnp.square(grads[k]) for k in params}
        new_params = {k: params[k] - kge["lr"] * grads[k]
                      / (jnp.sqrt(new_gsq[k]) + ADAGRAD_EPS) for k in params}
        norms = {k: jnp.linalg.norm(grads[k].astype(jnp.float32)) for k in grads}
    return new_params, new_gsq, value.astype(jnp.float32), norms


def train(kge: dict, seed: int, batches: list, dtype=jnp.float32) -> dict:
    """Run the given batches from the seed's weights. Returns each step's loss,
    each table's first gradient norm and each table's change after the last
    batch."""
    params = init_tables(kge, seed, dtype)
    start = {k: v.astype(jnp.float32) for k, v in params.items()}
    gsq = {k: jnp.zeros_like(v) for k, v in params.items()}
    items = tuple(sorted((k, v) for k, v in kge.items() if not isinstance(v, (dict, list))))
    losses, grad_norms = [], None
    for b in batches:
        dev = {k: jnp.asarray(v, jnp.int32) for k, v in b.items()}
        params, gsq, value, norms = _step(items, params, gsq, dev)
        losses.append(float(value))
        if grad_norms is None:
            grad_norms = {k: float(v) for k, v in norms.items()}
    change = {k: float(jnp.linalg.norm(params[k].astype(jnp.float32) - start[k]))
              for k in params}
    return {"loss": losses, "grad_norm": grad_norms, "change_norm": change}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _query_scores(model, corrupt, gamma, ent, rel, h, r, t):
    with jax.default_matmul_precision("highest"):
        hv, rv, tv = ent[h], rel[r], ent[t]
        return (positive_score(model, hv, rv, tv, gamma),
                candidate_scores(model, hv, rv, tv, ent, gamma, corrupt))


def query_scores(kge: dict, tables: dict, queries: np.ndarray, corrupt: str,
                 block: int = 8):
    """Positive scores (q,) and scores against every entity (q, N), in blocks
    of ``block`` queries so that the (block, N, d) differences fit."""
    pos, cand = [], []
    for i in range(0, queries.shape[0], block):
        q = queries[i : i + block]
        p, c = _query_scores(kge["model"], corrupt, kge["gamma"], tables["entity"],
                             tables["rel"], *(jnp.asarray(q[:, j], jnp.int32)
                                              for j in range(3)))
        pos.append(np.asarray(p.astype(jnp.float32)))
        cand.append(np.asarray(c.astype(jnp.float32)))
    return np.concatenate(pos), np.concatenate(cand)
