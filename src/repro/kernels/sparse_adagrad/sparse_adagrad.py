"""Pallas TPU kernel: fused sparse-Adagrad tile update.

The update half of every DGL-KE step (paper §2, §3.4) is a per-row Adagrad
over the deduplicated touched rows. The jnp path costs ~4 HBM passes over
those rows (scatter-add into gsq, gather of the updated accumulator,
scatter-add into the table) plus the argsort/segment_sum dedup machinery.
One kernel fuses the update; its wrapper (ops.fused_sparse_adagrad) sums
duplicate rows in the same sort and scatter that groups rows by tile.

``fused_update_pallas``
    One pass per touched *tile*: a tile is the ``tr``-row group of the table
    that the TPU lays out as one (8, 128) memory tile (``tr`` = 8 rows for
    32-bit tables, 16 for 16-bit), the smallest row block Mosaic can DMA or
    window. For each touched tile the kernel reads the gradient tile, the
    table tile and the accumulator tile, computes ``gsq += g²`` and the
    Adagrad step from the *updated* accumulator (DGL-KE order) on the rows
    the ``mask`` marks, and writes both tiles back with every other row
    bit-identical. ``table`` and ``gsq`` are HBM-aliased outputs
    (``input_output_aliases``) so untouched tiles are never copied. Tiles are
    addressed through scalar-prefetched tile ids (the ``index_map`` gathers
    block ``rmap[i]`` of the full table).

    Hazard contract (enforced by the wrapper): valid tile ids MUST be
    unique — the block pipeline prefetches ahead, so a duplicate tile would
    be re-read before the previous write lands. The wrapper groups the row
    ids by tile, which makes the tile ids unique. Pad slots (tile id < 0)
    are remapped by the wrapper to the *previous* valid slot's tile:
    consecutive same-index blocks stay resident in VMEM (no
    refetch/reflush), and the kernel simply skips the write, so a pad is a
    true no-op with no read-after-write hazard.

Grid order (revisit-safety): ``(D/bd, T)`` with d OUTERMOST — within one
d-column, pad slots revisit the immediately preceding block; across columns
blocks never alias.

Every BlockSpec keeps its last two block dimensions divisible by (8, 128)
or equal to the array's, the rule Mosaic enforces when it compiles for the
chip (tests/test_tpu_compile.py compiles the kernel at FB15k widths).

The kernel is named after the device scope its wrapper gives it
(``kge.adagrad_update``), so its custom call carries the scope in its own
name: a v5e profile names each op by its HLO text, which holds no scope
metadata.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# fused tile update
# ---------------------------------------------------------------------------
def _update_kernel(rmap_ref, tids_ref, g_ref, m_ref, t_ref, q_ref, to_ref,
                   qo_ref, *, lr: float, eps: float):
    del rmap_ref  # consumed by the index maps
    i = pl.program_id(1)
    valid = tids_ref[i] >= 0

    # Pad slots skip the write entirely: their block is the (resident)
    # previous valid slot's block, whose out_ref already holds the update.
    # i == 0 must write even when padded (first visit of the chain — out_ref
    # is uninitialized); with no row marked that write is a bitwise copy.
    @pl.when(jnp.logical_or(valid, i == 0))
    def _():
        hit = jnp.logical_and(m_ref[...] > 0, valid)  # (tr, 1) rows to update
        t, q = t_ref[...], q_ref[...]
        g = g_ref[...].astype(jnp.float32)
        q_new = q.astype(jnp.float32) + g * g
        t_new = t.astype(jnp.float32) - lr * g / (jnp.sqrt(q_new) + eps)
        qo_ref[...] = jnp.where(hit, q_new.astype(qo_ref.dtype), q)
        to_ref[...] = jnp.where(hit, t_new.astype(to_ref.dtype), t)


def fused_update_pallas(
    table: jnp.ndarray,
    gsq: jnp.ndarray,
    rmap: jnp.ndarray,
    tile_ids: jnp.ndarray,
    grad_tiles: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    lr: float,
    eps: float = 1e-10,
    bd: int = 0,
    interpret: bool = False,
):
    """In-place sparse Adagrad over whole row tiles (see ops.py for the
    grouping). ``tile_ids`` (T,) unique tile ids with -1 pads, ``rmap`` their
    pad-remapped form; ``grad_tiles`` (T*tr, D) and ``mask`` (T*tr, 1) hold
    row ``r`` of tile slot ``s`` at ``s*tr + r``.

    ``bd`` must divide D and be D or a multiple of 128; 0 = whole row per
    block. Returns (table, gsq) — the same HBM buffers, updated in place via
    input_output_aliases.
    """
    T = tile_ids.shape[0]
    tr = grad_tiles.shape[0] // T
    D = table.shape[1]
    bd = bd or D
    assert D % bd == 0
    slot = lambda d, i, rmap, tids: (i, d)  # noqa: E731
    tile = lambda d, i, rmap, tids: (rmap[i], d)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(D // bd, T),
        in_specs=[
            pl.BlockSpec((tr, bd), slot),
            pl.BlockSpec((tr, 1), lambda d, i, rmap, tids: (i, 0)),
            pl.BlockSpec((tr, bd), tile),
            pl.BlockSpec((tr, bd), tile),
        ],
        out_specs=[pl.BlockSpec((tr, bd), tile),
                   pl.BlockSpec((tr, bd), tile)],
    )
    return pl.pallas_call(
        functools.partial(_update_kernel, lr=lr, eps=eps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(table.shape, table.dtype),
                   jax.ShapeDtypeStruct(gsq.shape, gsq.dtype)],
        # operand order: rmap, tids, grads, mask, table, gsq -> alias table/gsq
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
        name="kge.adagrad_update",
    )(rmap, tile_ids, grad_tiles, mask, table, gsq)
