"""Compile the main-path Pallas kernels for a described TPU v5e, no chip needed.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block shapes off the (8, 128) tiling, batched 3-D dot_generals, kernels
that need more VMEM than the chip has. Here each kernel is lowered and
compiled for one chip of a described ``v5e:2x2`` topology at FB15k's
shapes (d=400, and d=2000 of the exemplar's wide config; n=3,584 workspace
rows = 3b + 2k at b=1024, k=256).

The topology is described inside a module-scoped fixture, never at import,
so every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.kge_score.kge_score import l1_bwd_pallas, pairwise_pallas
from repro.kernels.kge_score.ops import _tiles
from repro.kernels.sparse_adagrad.ops import (
    _tile_rows, dedup_aggregate, fused_sparse_adagrad,
)

N_ENTITIES = 14_951  # FB15k (configs/kge_datasets.py)
N_WORKSPACE = 3_584
B, K = 1_024, 256
WIDTHS = (400, 2000)


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


@pytest.mark.parametrize("d", WIDTHS)
def test_fused_sparse_adagrad_compiles_in_place(row_major, d):
    table = row_major((N_ENTITIES, d))
    compiled = _compile(
        lambda t, q, i, g: fused_sparse_adagrad(t, q, i, g, 0.1,
                                                interpret=False),
        table, table, row_major((N_WORKSPACE,), jnp.int32),
        row_major((N_WORKSPACE, d)),
        donate_argnums=(0, 1), out_shardings=(row_major.fmt(2),) * 2)
    mem = compiled.memory_analysis()
    table_bytes = N_ENTITIES * d * 4
    # table and gsq are updated in the donated buffers ...
    assert mem.alias_size_in_bytes >= 2 * table_bytes
    # ... and the only large temporary is the gradient-tile buffer: one
    # (8, d) tile per touched tile, at most min(n, N/8) of them. A copy of
    # the table would add another table_bytes on top.
    tr = _tile_rows(jnp.float32)
    n_tiles = min(N_WORKSPACE, -(-N_ENTITIES // tr))
    d_lanes = -(-d // 128) * 128
    assert mem.temp_size_in_bytes <= n_tiles * tr * d_lanes * 4 + (4 << 20)


@pytest.mark.parametrize("d", WIDTHS)
def test_dedup_aggregate_compiles(row_major, d):
    _compile(lambda i, g: dedup_aggregate(i, g, interpret=False),
             row_major((N_WORKSPACE,), jnp.int32), row_major((N_WORKSPACE, d)))


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
