"""jit'd wrapper for the sparse-Adagrad kernel: grouping, pad-remap, tiles.

``fused_sparse_adagrad`` is the fused replacement for the jnp
``segment-dedup → sparse_adagrad_update_rows`` pair: it takes raw workspace
ids, sums the gradients of duplicate rows while grouping them by tile
(``_group_tiles``), and updates each touched tile once. optim/sparse_adagrad.py
routes through it behind its ``use_kernel`` flag — nothing else should call
it.

Contract: any ids — duplicates allowed (their gradients are summed in float32
before the one Adagrad step of the row), pad slots (id < 0) anywhere, exact
no-ops. The table is never padded or copied — the kernel updates the aliased
buffers in place, one memory tile of ``tr`` rows at a time.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.compat import interpret_kernels as _interpret
from repro.kernels.sparse_adagrad.sparse_adagrad import fused_update_pallas
from repro.optim.sparse_adagrad import add_rows


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
    """Group row ids by the ``tr``-row tile that holds them, summing the
    gradients of duplicate ids.

    Returns ``(tile_ids (n_tiles,), grad_tiles (n_tiles*tr, D) float32,
    mask (n_tiles*tr, 1))``: the distinct touched tiles, ascending and
    compacted with -1 pads, and row ``r`` of slot ``s`` at ``s*tr + r``
    holding the sum of that row's gradients (mask 1) or nothing (mask 0).
    Every occurrence of a row id lands on the same (slot, row) pair, so the
    scatter-add (``add_rows``, in column blocks for wide rows) is the whole
    duplicate aggregation. The ids are sorted with pads last, so the
    destinations never decrease and the scatters are told so
    (``indices_are_sorted``).
    """
    n, D = grads.shape
    valid = ids >= 0
    order = jnp.argsort(jnp.where(valid, ids, jnp.iinfo(jnp.int32).max))
    sv, sid = valid[order], ids[order]
    st = sid // tr
    first = sv & jnp.concatenate([jnp.ones((1,), bool), st[1:] != st[:-1]])
    slot = jnp.cumsum(first) - 1
    tile_ids = jnp.full((n_tiles,), -1, jnp.int32).at[
        jnp.where(first, slot, n_tiles)].set(st, mode="drop")
    dest = jnp.where(sv, slot * tr + sid % tr, n_tiles * tr)
    grad_tiles = add_rows(n_tiles * tr, dest, grads[order],
                          indices_are_sorted=True)
    mask = jnp.zeros((n_tiles * tr, 1), jnp.int32).at[dest].set(
        1, mode="drop", indices_are_sorted=True)
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
    """Fused in-place row update. ids (n,): duplicates and -1 pads allowed.

    The grouping (sort and scatter-add) runs under the device scope
    ``kge.adagrad_dedup``, the kernel under ``kge.adagrad_update``.
    """
    if ids.shape[0] == 0:
        return table, gsq
    interpret = _interpret() if interpret is None else interpret
    tr = _tile_rows(table.dtype)
    n_tiles = min(ids.shape[0], -(-table.shape[0] // tr))
    with jax.named_scope("kge.adagrad_dedup"):
        tile_ids, grad_tiles, mask = _group_tiles(
            ids.astype(jnp.int32), grads, tr, n_tiles)
    with jax.named_scope("kge.adagrad_update"):
        return fused_update_pallas(
            table, gsq, _pad_remap(tile_ids), tile_ids,
            grad_tiles.astype(table.dtype), mask, lr=lr, eps=eps,
            bd=_row_tile(table.shape[1]), interpret=interpret)
