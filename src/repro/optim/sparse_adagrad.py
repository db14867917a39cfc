"""Sparse per-row Adagrad — the optimizer DGL-KE uses for embeddings.

DGL-KE performs *sparse gradient updates* (paper §2, §3.4): only the embedding
rows touched by a mini-batch are read, adjusted by Adagrad, and written back.
Here the same contract is expressed as functional row updates suitable for
``jnp.ndarray.at[ids]`` scatter application on a sharded table.

Two implementations sit behind one entry point, ``sparse_adagrad_apply``
(the only function ``EmbeddingStore.apply_sparse_grads`` calls):

* the **jnp path** — argsort + ``segment_sum`` dedup followed by scatter-add
  row updates (≈4 HBM passes over the touched rows per table per step);
* the **fused Pallas path** (kernels/sparse_adagrad) — a tiled on-device
  dedup-aggregate plus ONE pass per touched 8-row memory tile that reads the
  aggregated gradients, bumps ``gsq``, computes the step from the *updated*
  accumulator (the DGL-KE order) and writes the tile back, with ``table``
  and ``gsq`` aliased in place.

Which path runs is the ``use_kernel`` flag: ``None`` (default) means the
kernels on a TPU backend and jnp otherwise — overridable per-process with
``set_use_kernel`` or the ``REPRO_SPARSE_ADAGRAD_KERNEL`` env var (0/1). The flag is read at *trace*
time: already-jitted step functions keep the path they were traced with.

Padding convention: ids equal to ``pad_id`` (< 0 after masking, remapped to
row 0 with zero gradient) are no-ops, enabling fixed-size buffers under jit.
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common import compat, telemetry


class AdagradState(NamedTuple):
    # per-element accumulated squared gradients, same shape as the table
    gsq: jnp.ndarray


def sparse_adagrad_init(table: jnp.ndarray) -> AdagradState:
    return AdagradState(gsq=jnp.zeros_like(table))


# --------------------------------------------------------------------------
# kernel-vs-jnp dispatch
# --------------------------------------------------------------------------
_USE_KERNEL_OVERRIDE: Optional[bool] = None


def set_use_kernel(flag: Optional[bool]) -> None:
    """Force (True/False) or restore the platform default (None) of the
    fused kernel.

    Takes effect at the next trace — functions already jitted keep the path
    they were traced with (build step functions after flipping the flag).
    """
    global _USE_KERNEL_OVERRIDE
    _USE_KERNEL_OVERRIDE = flag


def use_kernel() -> bool:
    """Resolve the kernel flag (see module docstring)."""
    if _USE_KERNEL_OVERRIDE is not None:
        return _USE_KERNEL_OVERRIDE
    env = os.environ.get("REPRO_SPARSE_ADAGRAD_KERNEL")
    if env is not None:
        return env.lower() not in ("0", "false", "")
    return compat.backend() == "tpu"


def _resolve(flag: Optional[bool]) -> bool:
    return use_kernel() if flag is None else flag


# --------------------------------------------------------------------------
# dedup / aggregation
# --------------------------------------------------------------------------
def segment_aggregate_rows(
    ids: jnp.ndarray, grads: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort-based dedup: returns (unique ids, summed grads), compacted.

    ``ids``: (n,) int32 row ids (may repeat, < 0 = pad); ``grads``: (n, d).
    Output keeps the fixed size n: the unique ids sit in the leading slots
    (sorted ascending), every remaining slot holds pad -1 with an arbitrary
    (ignored) gradient row.
    """
    n = ids.shape[0]
    order = jnp.argsort(ids)
    sids = ids[order]
    sg = grads[order]
    first = jnp.concatenate([jnp.array([True]), sids[1:] != sids[:-1]])
    seg = jnp.cumsum(first) - 1  # segment index per sorted row
    agg = jax.ops.segment_sum(sg, seg, num_segments=n)
    uid = jax.ops.segment_max(jnp.where(first, sids, -1), seg, num_segments=n)
    slot_valid = jnp.arange(n) < jnp.sum(first)
    uid = jnp.where(slot_valid, uid, -1)
    return uid.astype(jnp.int32), agg


def aggregate_rows(
    ids: jnp.ndarray,
    grads: jnp.ndarray,
    use_kernel: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dedup duplicate ids, summing their gradient rows.

    Both paths return fixed-size (uid, agg) where each surviving slot holds a
    unique id with the aggregated gradient and every other slot holds -1;
    layouts differ (jnp compacts+sorts, the kernel keeps original positions)
    but both are valid inputs to ``sparse_adagrad_update_rows`` /
    ``fused_sparse_adagrad``, which ignore slot order.
    """
    if _resolve(use_kernel):
        from repro.kernels.sparse_adagrad import dedup_aggregate

        return dedup_aggregate(ids.astype(jnp.int32), grads)
    return segment_aggregate_rows(ids.astype(jnp.int32), grads)


def dedup_compact_rows(
    ids: jnp.ndarray,
    grads: jnp.ndarray,
    capacity: int,
    use_kernel: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dedup + compact into a ``capacity``-slot buffer (T5 pend buffers).

    Returns (ids (capacity,), grads (capacity, d), n_dropped). Uniques beyond
    ``capacity`` are DROPPED (their gradients are lost) — callers size the
    buffer for the expected unique count and may surface ``n_dropped`` as a
    diagnostic; the deferred-update memory bound is the point (ROADMAP T5).
    """
    uid, agg = aggregate_rows(ids, grads, use_kernel)
    first = uid >= 0
    rank = jnp.cumsum(first) - 1
    dest = jnp.where(first, rank, capacity)  # non-uniques -> dropped slot
    out_ids = jnp.full((capacity,), -1, jnp.int32).at[dest].set(
        uid, mode="drop")
    out_grads = jnp.zeros((capacity,) + grads.shape[1:], grads.dtype).at[
        dest].set(agg.astype(grads.dtype), mode="drop")
    n_dropped = jnp.maximum(0, jnp.sum(first) - capacity)
    return out_ids, out_grads, n_dropped


# --------------------------------------------------------------------------
# row updates
# --------------------------------------------------------------------------
def sparse_adagrad_update_rows(
    table: jnp.ndarray,
    state: AdagradState,
    ids: jnp.ndarray,
    grad_rows: jnp.ndarray,
    lr: float,
    eps: float = 1e-10,
) -> Tuple[jnp.ndarray, AdagradState]:
    """Apply Adagrad to rows ``ids`` of ``table``. ids < 0 are padding no-ops.

    Duplicate-id hazard: valid ids MUST be unique. Adagrad is nonlinear —
    with duplicates the scatter-add sums every occurrence into ``gsq``
    *before* the step is computed, so each duplicate's step is divided by the
    full aggregated denominator and the rows double-count it; the fused
    Pallas kernel additionally has a read-after-write pipeline hazard on
    duplicate rows. Dedup (``aggregate_rows``) must precede this call —
    ``sparse_adagrad_apply`` composes the two correctly.
    """
    valid = (ids >= 0)[:, None]
    safe_ids = jnp.maximum(ids, 0)
    g = jnp.where(valid, grad_rows, 0.0).astype(table.dtype)
    new_gsq = state.gsq.at[safe_ids].add(jnp.square(g), mode="drop")
    # read back the *updated* accumulator for the step size (DGL-KE order)
    denom = jnp.sqrt(new_gsq[safe_ids]) + eps
    step = jnp.where(valid, lr * g / denom, 0.0)
    new_table = table.at[safe_ids].add(-step, mode="drop")
    return new_table, AdagradState(gsq=new_gsq)


def sparse_adagrad_apply(
    table: jnp.ndarray,
    gsq: jnp.ndarray,
    ids: jnp.ndarray,
    grads: jnp.ndarray,
    lr: float,
    eps: float = 1e-10,
    use_kernel: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """THE sparse update: dedup-aggregate then per-row Adagrad.

    Accepts raw (possibly duplicated, possibly padded) workspace ids; every
    ``EmbeddingStore.apply_sparse_grads`` lowers to this call, which picks
    the fused Pallas path or the jnp path per the ``use_kernel`` flag.
    """
    ids = ids.astype(jnp.int32)
    if _resolve(use_kernel):
        # dispatch decisions happen at trace time — the counters say which
        # path each traced step function took (docs/TELEMETRY.md)
        telemetry.inc("optim/dispatch_fused")
        from repro.kernels.sparse_adagrad import (
            dedup_aggregate, fused_sparse_adagrad,
        )

        uid, agg = dedup_aggregate(ids, grads)
        return fused_sparse_adagrad(table, gsq, uid, agg, lr, eps)
    telemetry.inc("optim/dispatch_jnp")
    uid, agg = segment_aggregate_rows(ids, grads)
    new_table, st = sparse_adagrad_update_rows(
        table, AdagradState(gsq), uid, agg, lr, eps)
    return new_table, st.gsq


def dense_adagrad_update(
    table: jnp.ndarray,
    state: AdagradState,
    grad: jnp.ndarray,
    lr: float,
    eps: float = 1e-10,
) -> Tuple[jnp.ndarray, AdagradState]:
    """Dense reference (what treating embeddings as dense weights costs —
    the PBG behaviour the paper §3.4 argues against). Also the update rule of
    ``ReplicatedStore`` after its cross-machine gradient psum."""
    gsq = state.gsq + jnp.square(grad)
    new_table = table - lr * grad / (jnp.sqrt(gsq) + eps)
    return new_table, AdagradState(gsq=gsq)
