"""Compile the main-path Pallas kernels for a described TPU v5e, no chip needed.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block shapes off the (8, 128) tiling, batched 3-D dot_generals, kernels
that need more VMEM than the chip has. Here each kernel is lowered and
compiled for one chip of a described ``v5e:2x2`` topology at FB15k's
shapes (d=400, and d=2000 of the exemplar's wide config; n=3,584 workspace
rows = 3b + 2k at b=1024, k=256), and TransR's at DGL-KE's FB15k widths
(d = rel_dim = 200: the projected-scoring kernels per group of 256
triplets and 256 negatives, and the update of the 1,345 x 40,000
projection table from n=1,024 relation ids).

The topology is described inside a module-scoped fixture, never at import,
so every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.kge_score.kge_score import (
    l1_bwd_pallas,
    pairwise_pallas,
    transr_bwd_pallas,
    transr_fwd_pallas,
)
from repro.kernels.kge_score.ops import _tiles
from repro.kernels.sparse_adagrad.ops import _tile_rows, fused_sparse_adagrad

N_ENTITIES = 14_951  # FB15k (configs/kge_datasets.py)
N_WORKSPACE = 3_584
B, K = 1_024, 256
WIDTHS = (400, 2000)
# TransR at FB15k: 1,345 projections of 200 x 200, one per relation id of a
# batch of 1,024; 4 groups of 256 triplets share 256 negatives
N_RELATIONS, TRANSR_D, GROUPS = 1_345, 200, 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def row_major(topo):
    """Shape builder pinned to the default row-major layout on one chip (a
    described chip otherwise lets XLA pick any layout, which real arrays
    never have)."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def fmt(ndim):
        return Format(Layout(major_to_minor=tuple(range(ndim))), one_chip)

    def shape(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=fmt(len(shape)))

    shape.fmt = fmt
    return shape


def _compile(fn, *args, **jit_kw):
    compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("rows,d,n,wide", [
    pytest.param(N_ENTITIES, d, N_WORKSPACE, False, id=str(d)) for d in WIDTHS] + [
    # rows wider than add_rows' column block: the blocks are scattered apart
    # and put side by side, and XLA keeps the gradients in id order as a
    # buffer of their own instead of fusing that gather into the scatter-add
    pytest.param(N_RELATIONS, TRANSR_D * TRANSR_D, B, True, id="transr_proj")])
def test_fused_sparse_adagrad_compiles_in_place(row_major, rows, d, n, wide):
    """The raw workspace ids go in, duplicates and all: the grouping's
    scatter-add sums duplicate rows with no table-sized temporary."""
    table = row_major((rows, d))
    compiled = _compile(
        lambda t, q, i, g: fused_sparse_adagrad(t, q, i, g, 0.1,
                                                interpret=False),
        table, table, row_major((n,), jnp.int32), row_major((n, d)),
        donate_argnums=(0, 1), out_shardings=(row_major.fmt(2),) * 2)
    # every scatter-add runs in parallel: XLA turns one of rows 32,000 floats
    # wide or wider into a serial loop (optim/sparse_adagrad.add_rows)
    assert "while(" not in compiled.as_text()
    mem = compiled.memory_analysis()
    table_bytes = rows * d * 4
    # table and gsq are updated in the donated buffers ...
    assert mem.alias_size_in_bytes >= 2 * table_bytes
    # ... and the only large temporary is the gradient-tile buffer: one
    # (8, d) tile per touched tile, at most min(n, N/8) of them (for wide
    # rows, twice over, and the (n, d) gradients in id order). A copy of the
    # table would add another table_bytes on top.
    tr = _tile_rows(jnp.float32)
    n_tiles = min(n, -(-rows // tr))
    d_lanes = -(-d // 128) * 128
    grad_rows = n_tiles * tr + (n_tiles * tr + n if wide else 0)
    assert mem.temp_size_in_bytes <= grad_rows * d_lanes * 4 + (4 << 20)


@pytest.mark.parametrize("d", WIDTHS)
@pytest.mark.parametrize("mode", ["l2sq", "dot", "l1"])
def test_pairwise_forward_compiles(row_major, mode, d):
    bm, bn, bk = _tiles(B, K, d, mode)
    d_pad = -(-d // bk) * bk
    _compile(lambda o, n: pairwise_pallas(o, n, mode, bm=bm, bn=bn, bk=bk),
             row_major((B, d_pad)), row_major((K, d_pad)))


@pytest.mark.parametrize("d", WIDTHS)
def test_l1_backward_compiles(row_major, d):
    bm, bn, bk = _tiles(B, K, d, "l1")
    d_pad = -(-d // bk) * bk
    compiled = _compile(
        lambda o, n, g: l1_bwd_pallas(o, n, g, bm=bm, bn=bn, bk=bk),
        row_major((B, d_pad)), row_major((K, d_pad)), row_major((B, K)))
    assert compiled.as_text().count("tpu_custom_call") >= 2  # d_o and d_n


def test_transr_score_kernels_compile(row_major):
    """kge.transr_score and kge.transr_score_bwd at FB15k TransR's group
    shapes, bfloat16 products as on the chip."""
    g, k, d = GROUPS, K, TRANSR_D
    gsz = B // GROUPS
    # r-major operands: o_t (G, R, B), m_t (G, R, D, B)
    o_t, negs, m_t = row_major((g, d, gsz)), row_major((g, k, d)), row_major((g, d, d, gsz))
    fwd = _compile(lambda *a: transr_fwd_pallas(*a, mxu_dtype=jnp.bfloat16), o_t, negs, m_t)
    assert "kge.transr_score" in fwd.as_text()
    bwd = _compile(lambda *a: transr_bwd_pallas(*a, mxu_dtype=jnp.bfloat16),
                   o_t, negs, m_t, row_major((g, k, gsz)))
    assert "kge.transr_score_bwd" in bwd.as_text()
