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
        name=f"kge_pairwise_{mode}",
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
        name="kge_l1_bwd_do",
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
        name="kge_l1_bwd_dn",
    )(o, negs, g)
    return do, dn


# ---------------------------------------------------------------------------
# TransR: per-triplet projected distances to the group's shared negatives.
# Each candidate must be projected by the matrix of every triplet of its
# group, so there is no shared-candidate GEMM; the (B, K, R) projections
# live only in VMEM, one projected dimension r at a time.
#
# Layout: the matrices come r-major, m_t (G, R, D, B), and the vectors o as
# o_t (G, R, B), so that for each r the projections of all K candidates by
# all B matrices are one (K, D) x (D, B) product, lane-dense in B, and the
# squared distance accumulates into a resident (kc, B) block of the (G, K, B)
# output over the innermost grid axis, which walks ``rb`` rows of R. Grid
# (G, K/kc, R/rb). Matrix operands are cast to ``mxu_dtype``; products
# accumulate in float32.
# ---------------------------------------------------------------------------
def _transr_fwd_kernel(o_ref, n_ref, m_ref, out_ref, *, rb: int, mxu_dtype):
    r = pl.program_id(2)
    negs = n_ref[0].astype(mxu_dtype)  # (kc, D)
    acc = jnp.where(r == 0, 0.0, out_ref[0])  # (kc, B)
    for i in range(rb):
        pn = jnp.dot(negs, m_ref[0, i].astype(mxu_dtype),
                     preferred_element_type=jnp.float32)  # (kc, B)
        diff = o_ref[0, i : i + 1, :].astype(jnp.float32) - pn
        acc = acc + diff * diff
    out_ref[0] = acc


def _transr_bwd_kernel(o_ref, n_ref, nt_ref, m_ref, g_ref, dm_ref, dn_ref,
                       do_ref, *, rb: int, mxu_dtype):
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        dn_ref[...] = jnp.zeros_like(dn_ref)

    negs = n_ref[0].astype(mxu_dtype)  # (kc, D)
    negs_t = nt_ref[0].astype(mxu_dtype)  # (D, kc)
    g = g_ref[0].astype(jnp.float32)  # (kc, B) upstream
    dn = jnp.zeros(dn_ref.shape[1:], jnp.float32)
    for i in range(rb):
        m = m_ref[0, i].astype(mxu_dtype)  # (D, B)
        pn = jnp.dot(negs, m, preferred_element_type=jnp.float32)
        diff = o_ref[0, i : i + 1, :].astype(jnp.float32) - pn  # (kc, B)
        dpn = (-2.0 * g) * diff  # d(loss)/d(pn)
        do_ref[0, 0, i : i + 1, :] = -jnp.sum(dpn, axis=0, keepdims=True)
        dpn_m = dpn.astype(mxu_dtype)
        dm_ref[0, 0, i] = jnp.dot(negs_t, dpn_m, preferred_element_type=jnp.float32)
        dn += jax.lax.dot_general(dpn_m, m, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    dn_ref[0] += dn


def _transr_specs(o_t, negs, kc: int, rb: int):
    G, R, B = o_t.shape
    K, D = negs.shape[1:]
    assert K % kc == 0 and R % rb == 0, (K, kc, R, rb)
    specs = {
        "o": pl.BlockSpec((1, rb, B), lambda g, c, r: (g, r, 0)),
        "negs": pl.BlockSpec((1, kc, D), lambda g, c, r: (g, c, 0)),
        "m": pl.BlockSpec((1, rb, D, B), lambda g, c, r: (g, r, 0, 0)),
        "scores": pl.BlockSpec((1, kc, B), lambda g, c, r: (g, c, 0)),
    }
    return (G, K // kc, R // rb), specs


def transr_fwd_pallas(o_t, negs, m_t, *, kc: int = 0, rb: int = 8,
                      mxu_dtype=jnp.float32, interpret: bool = False):
    """o_t (G, R, B), negs (G, K, D), m_t (G, R, D, B) -> (G, K, B) float32:
    entry [g, k, b] is sum_r (o[g, b] - negs[g, k] @ M[g, b])_r^2, with
    ``o_t[g, r, b] = o[g, b, r]`` and ``m_t[g, r, d, b] = M[g, b][d, r]``.
    ``kc`` (0 = K) must divide K and ``rb`` divide R."""
    grid, sp = _transr_specs(o_t, negs, kc or negs.shape[1], rb)
    G, _, B = o_t.shape
    return pl.pallas_call(
        functools.partial(_transr_fwd_kernel, rb=rb, mxu_dtype=mxu_dtype),
        grid=grid,
        in_specs=[sp["o"], sp["negs"], sp["m"]],
        out_specs=sp["scores"],
        out_shape=jax.ShapeDtypeStruct((G, negs.shape[1], B), jnp.float32),
        interpret=interpret,
        name="kge.transr_score",
    )(o_t, negs, m_t)


def transr_bwd_pallas(o_t, negs, m_t, g, *, kc: int = 0, rb: int = 8,
                      mxu_dtype=jnp.float32, interpret: bool = False):
    """Gradients of ``transr_fwd_pallas`` given its upstream ``g`` (G, K, B),
    the projections recomputed in VMEM. Returns float32 (d_m_t (G, K/kc, R,
    D, B), d_negs (G, K, D), d_o_t (G, K/kc, R, B)): d_m_t and d_o_t hold one
    partial sum per block of ``kc`` candidates."""
    G, R, B = o_t.shape
    K, D = negs.shape[1:]
    kc = kc or K
    grid, sp = _transr_specs(o_t, negs, kc, rb)
    return pl.pallas_call(
        functools.partial(_transr_bwd_kernel, rb=rb, mxu_dtype=mxu_dtype),
        grid=grid,
        in_specs=[sp["o"], sp["negs"],
                  pl.BlockSpec((1, D, kc), lambda g, c, r: (g, 0, c)),
                  sp["m"], sp["scores"]],
        out_specs=[
            pl.BlockSpec((1, 1, rb, D, B), lambda g, c, r: (g, c, r, 0, 0)),
            sp["negs"],
            pl.BlockSpec((1, 1, rb, B), lambda g, c, r: (g, c, r, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((G, K // kc, R, D, B), jnp.float32),
                   jax.ShapeDtypeStruct((G, K, D), jnp.float32),
                   jax.ShapeDtypeStruct((G, K // kc, R, B), jnp.float32)],
        interpret=interpret,
        name="kge.transr_score_bwd",
    )(o_t, negs, jnp.swapaxes(negs, 1, 2), m_t, g)
