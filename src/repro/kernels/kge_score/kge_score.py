"""Pallas TPU kernel: joint-negative pairwise KGE scores (paper §3.3, T1).

The joint-negative-sampling reformulation turns the b×k negative scores into
a pairwise reduction between the per-triplet vectors ``o`` (b, d) and the
shared negative pool (k, d):

    dot  : o @ negs.T                      (DistMult / ComplEx / RESCAL)
    l2sq : ||o_i||² - 2 o@negs.T + ||n_j||²  (TransE_l2 / RotatE / TransR)
    l1   : Σ_d |o_id - n_jd|               (TransE_l1)

``dot``/``l2sq`` ride the MXU (the GEMM the paper routes to "highly optimized
math libraries"); ``l1`` has no GEMM form and is tiled on the VPU. The D
(contraction) axis is the innermost grid dim — sequential on TPU — with a
float32 accumulator in the revisited output block.

Block sizes target v5e: 128-aligned M/N tiles for the MXU, D tiles sized so
(bm, bn, bk) L1 broadcasts stay well under VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pairwise_kernel(o_ref, n_ref, out_ref, *, mode: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    o = o_ref[...].astype(jnp.float32)  # (bm, bk)
    n = n_ref[...].astype(jnp.float32)  # (bn, bk)
    if mode == "dot":
        out_ref[...] += jax.lax.dot_general(
            o, n, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
    elif mode == "l2sq":
        g = jax.lax.dot_general(
            o, n, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        o2 = jnp.sum(o * o, axis=1, keepdims=True)  # (bm, 1)
        n2 = jnp.sum(n * n, axis=1)[None, :]  # (1, bn)
        out_ref[...] += o2 - 2.0 * g + n2
    elif mode == "l1":
        # VPU path: broadcast difference over the D tile
        diff = jnp.abs(o[:, None, :] - n[None, :, :])  # (bm, bn, bk)
        out_ref[...] += jnp.sum(diff, axis=2)
    else:
        raise ValueError(mode)


def pairwise_pallas(
    o: jnp.ndarray,
    negs: jnp.ndarray,
    mode: str,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """(B, D) x (K, D) -> (B, K). Caller pads B/K/D to tile multiples."""
    B, D = o.shape
    K, _ = negs.shape
    bm, bn, bk = min(bm, B), min(bn, K), min(bk, D)
    assert B % bm == 0 and K % bn == 0 and D % bk == 0
    grid = (B // bm, K // bn, D // bk)
    kern = functools.partial(_pairwise_kernel, mode=mode)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, K), jnp.float32),
        interpret=interpret,
    )(o, negs)


# ---------------------------------------------------------------------------
# L1 backward kernels (no GEMM form; jnp would materialize (B, K, D) in HBM —
# the exact data-movement blowup T1 exists to avoid).
# ---------------------------------------------------------------------------
def _l1_do_kernel(o_ref, n_ref, g_ref, out_ref):
    j = pl.program_id(2)  # K tiles innermost (sequential accumulation)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    o = o_ref[...].astype(jnp.float32)  # (bm, bk)
    n = n_ref[...].astype(jnp.float32)  # (bn, bk)
    g = g_ref[...].astype(jnp.float32)  # (bm, bn)
    s = jnp.sign(o[:, None, :] - n[None, :, :])  # (bm, bn, bk)
    # a broadcast-multiply-reduce on the VPU: Mosaic has no batched 3-D
    # dot_general, which is what an einsum over (m, n, d) would lower to
    out_ref[...] += jnp.sum(g[:, :, None] * s, axis=1)


def _l1_dn_kernel(o_ref, n_ref, g_ref, out_ref):
    i = pl.program_id(2)  # B tiles innermost

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    o = o_ref[...].astype(jnp.float32)  # (bm, bk)
    n = n_ref[...].astype(jnp.float32)  # (bn, bk)
    g = g_ref[...].astype(jnp.float32)  # (bm, bn)
    s = jnp.sign(o[:, None, :] - n[None, :, :])  # (bm, bn, bk)
    out_ref[...] -= jnp.sum(g[:, :, None] * s, axis=0)


def l1_bwd_pallas(o, negs, g, *, bm=128, bn=128, bk=128, interpret=False):
    B, D = o.shape
    K, _ = negs.shape
    bm, bn, bk = min(bm, B), min(bn, K), min(bk, D)
    do = pl.pallas_call(
        _l1_do_kernel,
        grid=(B // bm, D // bk, K // bn),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, d, j: (i, d)),
            pl.BlockSpec((bn, bk), lambda i, d, j: (j, d)),
            pl.BlockSpec((bm, bn), lambda i, d, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, d, j: (i, d)),
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        interpret=interpret,
    )(o, negs, g)
    dn = pl.pallas_call(
        _l1_dn_kernel,
        grid=(K // bn, D // bk, B // bm),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda j, d, i: (i, d)),
            pl.BlockSpec((bn, bk), lambda j, d, i: (j, d)),
            pl.BlockSpec((bm, bn), lambda j, d, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((bn, bk), lambda j, d, i: (j, d)),
        out_shape=jax.ShapeDtypeStruct((K, D), jnp.float32),
        interpret=interpret,
    )(o, negs, g)
    return do, dn
