"""jit'd wrappers for the sparse-Adagrad kernels: padding, pad-remap, tiles.

``fused_sparse_adagrad`` is a drop-in for the jnp
``segment-dedup → sparse_adagrad_update_rows`` pair when the ids are already
deduplicated; ``dedup_aggregate`` is the kernel replacement for the
argsort/segment_sum dedup itself. optim/sparse_adagrad.py routes through
these behind its ``use_kernel`` flag — nothing else should call them.

Contracts:
  * ``fused_sparse_adagrad``: valid ids must be UNIQUE (of a duplicated
    row only one gradient would land — see optim/sparse_adagrad.py). Pad slots
    (id < 0) may appear anywhere; they are exact no-ops. The table is never
    padded or copied — the kernel updates the aliased buffers in place, one
    memory tile of ``tr`` rows at a time (``_group_tiles``).
  * ``dedup_aggregate``: any ids (duplicates + pads); returns the in-place
    layout of ref.dedup_aggregate_ref.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.compat import interpret_kernels as _interpret
from repro.kernels.sparse_adagrad.sparse_adagrad import (
    dedup_aggregate_pallas,
    fused_update_pallas,
)


def _row_tile(D: int) -> int:
    """Largest MXU/VPU-friendly tile that divides D exactly (the table's D
    axis cannot be padded — it is updated in place)."""
    for t in (512, 256, 128):
        if D % t == 0:
            return t
    return D


def _pad_remap(ids: jnp.ndarray) -> jnp.ndarray:
    """Remap pad slots to the nearest *preceding* valid slot's id.

    This makes every pad step a consecutive revisit of an already-resident
    block (no refetch — the Pallas pipeline only moves blocks when the index
    map output changes), which is what makes pads hazard-free. Leading pads
    map to the first valid id; an all-pad batch maps to block 0 (the kernel
    then performs a bitwise no-op copy at step 0 only).
    """
    n = ids.shape[0]
    valid = ids >= 0
    pos = jnp.where(valid, jnp.arange(n, dtype=jnp.int32), -1)
    last_valid = jax.lax.cummax(pos)
    first_valid = jnp.argmax(valid)  # 0 when there is none
    rmap = jnp.where(last_valid >= 0, ids[jnp.maximum(last_valid, 0)],
                     ids[first_valid])
    return jnp.maximum(rmap, 0).astype(jnp.int32)


def _tile_rows(dtype) -> int:
    """Rows in one (8, 128) memory tile of the TPU: 8 for 32-bit rows, 16
    for 16-bit — the smallest row block Mosaic can DMA or window."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _group_tiles(ids: jnp.ndarray, grads: jnp.ndarray, tr: int,
                 n_tiles: int):
    """Group unique row ids by the ``tr``-row tile that holds them.

    Returns ``(tile_ids (n_tiles,), grad_tiles (n_tiles*tr, D),
    mask (n_tiles*tr, 1))``: the distinct touched tiles, ascending and
    compacted with -1 pads, and row ``r`` of slot ``s`` at ``s*tr + r``
    holding that row's gradient (mask 1) or nothing (mask 0). Distinct
    rows give distinct (slot, row) pairs, so the scatter never collides.
    """
    n, D = grads.shape
    valid = ids >= 0
    tile = jnp.where(valid, ids // tr, jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(tile)
    st, sv, sid = tile[order], valid[order], ids[order]
    first = sv & jnp.concatenate([jnp.ones((1,), bool), st[1:] != st[:-1]])
    slot = jnp.cumsum(first) - 1
    tile_ids = jnp.full((n_tiles,), -1, jnp.int32).at[
        jnp.where(first, slot, n_tiles)].set(st, mode="drop")
    dest = jnp.where(sv, slot * tr + sid % tr, n_tiles * tr)
    grad_tiles = jnp.zeros((n_tiles * tr, D), grads.dtype).at[dest].set(
        grads[order], mode="drop")
    mask = jnp.zeros((n_tiles * tr, 1), jnp.int32).at[dest].set(
        1, mode="drop")
    return tile_ids, grad_tiles, mask


def fused_sparse_adagrad(
    table: jnp.ndarray,
    gsq: jnp.ndarray,
    ids: jnp.ndarray,
    grads: jnp.ndarray,
    lr: float,
    eps: float = 1e-10,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused in-place row update. ids (n,) with -1 pads, valid ids unique."""
    if ids.shape[0] == 0:
        return table, gsq
    interpret = _interpret() if interpret is None else interpret
    tr = _tile_rows(table.dtype)
    n_tiles = min(ids.shape[0], -(-table.shape[0] // tr))
    tile_ids, grad_tiles, mask = _group_tiles(
        ids.astype(jnp.int32), grads.astype(table.dtype), tr, n_tiles)
    return fused_update_pallas(
        table, gsq, _pad_remap(tile_ids), tile_ids, grad_tiles, mask,
        lr=lr, eps=eps, bd=_row_tile(table.shape[1]), interpret=interpret)


def _dedup_tiles(n: int, D: int) -> Tuple[int, int, int]:
    bi = min(128, max(8, 1 << (n - 1).bit_length()))
    bd = min(128, max(8, 1 << (D - 1).bit_length())) if D < 128 \
        else _row_tile(D) if D % 128 == 0 else 128
    return bi, bi, bd


def dedup_aggregate(
    ids: jnp.ndarray,
    grads: jnp.ndarray,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Kernel dedup: (uid, agg) in the in-place layout (see ref.py).

    Slot i keeps ids[i] iff it is the first occurrence; its grad row becomes
    the sum over all occurrences; other slots get (-1, zeros).
    """
    n = ids.shape[0]
    if n == 0:
        return ids.astype(jnp.int32), grads
    interpret = _interpret() if interpret is None else interpret
    D = grads.shape[1]
    bi, bj, bd = _dedup_tiles(n, D)
    npad = (-n) % max(bi, bj)
    dpad = (-D) % bd
    idp = jnp.pad(ids.astype(jnp.int32), (0, npad), constant_values=-1)
    gp = jnp.pad(grads, ((0, npad), (0, dpad)))
    agg, cnt = dedup_aggregate_pallas(idp, gp, bi=bi, bj=bj, bd=bd,
                                      interpret=interpret)
    agg, cnt = agg[:n, :D], cnt[:n, 0]
    first = (cnt == 0) & (ids >= 0)
    uid = jnp.where(first, ids, -1).astype(jnp.int32)
    return uid, jnp.where(first[:, None], agg, 0.0).astype(grads.dtype)
