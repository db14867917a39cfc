"""Pure-jnp oracles for the sparse-Adagrad kernel suite.

Contracts (mirrored by ops.py, matching optim/sparse_adagrad.py semantics):

``fused_update_ref(table, gsq, ids, grads, lr, eps)``
    For each slot i with ids[i] >= 0 (ids must be unique among valid slots):
        gsq[ids[i]]   += grads[i]²
        table[ids[i]] -= lr * grads[i] / (sqrt(updated gsq[ids[i]]) + eps)
    Slots with ids[i] < 0 are no-ops. Updates use the *updated* accumulator
    (the DGL-KE §3.4 order). Returns (new_table, new_gsq).

With duplicated ids, ``optim.sparse_adagrad.segment_aggregate_rows``
followed by this oracle is what ``ops.fused_sparse_adagrad`` computes.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp


def fused_update_ref(
    table: jnp.ndarray,
    gsq: jnp.ndarray,
    ids: jnp.ndarray,
    grads: jnp.ndarray,
    lr: float,
    eps: float = 1e-10,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    valid = (ids >= 0)[:, None]
    safe = jnp.maximum(ids, 0)
    g = jnp.where(valid, grads.astype(jnp.float32), 0.0)
    new_gsq = gsq.astype(jnp.float32).at[safe].add(jnp.square(g), mode="drop")
    denom = jnp.sqrt(new_gsq[safe]) + eps
    step = jnp.where(valid, lr * g / denom, 0.0)
    new_table = table.astype(jnp.float32).at[safe].add(-step, mode="drop")
    return new_table.astype(table.dtype), new_gsq.astype(gsq.dtype)

