"""jit'd wrapper for the kge_score kernel: padding, custom VJP, CPU fallback.

``kernel_pairwise_fn`` is a drop-in for core/scores.pairwise_scores — pass it
as ``pairwise_fn`` to negative_score / the train steps to route the T1 hot
loop through the Pallas kernel.

Backward:
  dot  : d_o = g @ negs ; d_n = g.T @ o                 (plain GEMMs — XLA)
  l2sq : d_o = 2 (o · rowsum(g) − g @ negs) ; symmetric (plain GEMMs)
  l1   : Pallas kernels (kge_score.l1_bwd_pallas) — the jnp form would
         materialize (B, K, D) in HBM.

``transr_l2sq`` is TransR's projected form (core/scores.projected_l2sq on a
TPU): one forward kernel and one backward kernel that recomputes the
projections, so the (G, B, K, R) projected negatives never reach HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common.compat import interpret_kernels as _interpret
from repro.kernels.kge_score.kge_score import (
    l1_bwd_pallas,
    pairwise_pallas,
    transr_bwd_pallas,
    transr_fwd_pallas,
)


def _pad_to(x: jnp.ndarray, m0: int, m1: int) -> jnp.ndarray:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def _tiles(B: int, K: int, D: int, mode: str):
    # MXU-aligned for GEMM modes; smaller D tiles for the VPU L1 path.
    # bk is capped at D's 128-aligned padding so a large cap never forces
    # padding beyond one tile (e.g. D=300 pads to 384, not 512).
    bm = 128 if B >= 128 else max(8, 1 << (B - 1).bit_length())
    bn = 128 if K >= 128 else max(8, 1 << (K - 1).bit_length())
    cap = 128 if mode == "l1" else 512
    dp = max(8, 1 << (D - 1).bit_length()) if D < 128 else -(-D // 128) * 128
    return bm, bn, min(cap, dp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def pairwise_scores_kernel(mode: str, o: jnp.ndarray, negs: jnp.ndarray):
    """(B, D) x (K, D) -> (B, K), matching core/scores.pairwise_scores."""
    return _fwd_impl(mode, o, negs)


def _fwd_impl(mode, o, negs):
    B, D = o.shape
    K = negs.shape[0]
    bm, bn, bk = _tiles(B, K, D, mode)
    op = _pad_to(o.astype(jnp.float32), bm, bk)
    np_ = _pad_to(negs.astype(jnp.float32), bn, bk)
    out = pairwise_pallas(op, np_, mode, bm=bm, bn=bn, bk=bk, interpret=_interpret())
    return out[:B, :K]


def _fwd(mode, o, negs):
    return _fwd_impl(mode, o, negs), (o, negs)


def _bwd(mode, res, g):
    o, negs = res
    g = g.astype(jnp.float32)
    if mode == "dot":
        return g @ negs, g.T @ o
    if mode == "l2sq":
        d_o = 2.0 * (o * jnp.sum(g, axis=1, keepdims=True) - g @ negs)
        d_n = 2.0 * (negs * jnp.sum(g, axis=0)[:, None] - g.T @ o)
        return d_o, d_n
    if mode == "l1":
        B, D = o.shape
        K = negs.shape[0]
        bm, bn, bk = _tiles(B, K, D, mode)
        op = _pad_to(o.astype(jnp.float32), bm, bk)
        np_ = _pad_to(negs.astype(jnp.float32), bn, bk)
        gp = _pad_to(g, bm, bn)
        d_o, d_n = l1_bwd_pallas(
            op, np_, gp, bm=bm, bn=bn, bk=bk, interpret=_interpret()
        )
        return d_o[:B, :D], d_n[:K, :D]
    raise ValueError(mode)


pairwise_scores_kernel.defvjp(_fwd, _bwd)


def kernel_pairwise_fn(mode: str, o: jnp.ndarray, negs: jnp.ndarray):
    """Drop-in ``pairwise_fn`` for core/scores.negative_score."""
    return pairwise_scores_kernel(mode, o, negs)


# ---------------------------------------------------------------------------
# TransR projected distances
# ---------------------------------------------------------------------------
def _mxu_dtype():
    """The operand precision a default-precision float32 matmul gets on the
    platform: one bfloat16 pass on a TPU, float32 elsewhere — what the jnp
    einsum the kernels replace gets, so the two differ in summation order."""
    return jnp.float32 if _interpret() else jnp.bfloat16


def _transr_blocks(R: int, K: int):
    """(padded R, padded K, kc): R in blocks of 8 rows; candidates in blocks
    of up to 512 (evaluation scores every entity)."""
    kp = K if K <= 512 else -(-K // 512) * 512
    return -(-R // 8) * 8, kp, min(kp, 512)


def _pad(x, axis, size):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return x if size == x.shape[axis] else jnp.pad(x, pad)


def _r_major(o, negs, m):
    """o (G, B, R) -> o_t (G, Rp, B); m (G, B, D, R) -> m_t (G, Rp, D, B);
    negs padded to Kp candidates. Zero rows of R add nothing to a distance."""
    rp, kp, kc = _transr_blocks(o.shape[2], negs.shape[1])
    o_t = _pad(jnp.swapaxes(o, 1, 2), 1, rp)
    m_t = _pad(jnp.transpose(m, (0, 3, 2, 1)), 1, rp)
    return o_t, _pad(negs, 1, kp), m_t, kc


@jax.custom_vjp
def transr_l2sq(o: jnp.ndarray, negs: jnp.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    """(G, B, R), (G, K, D), (G, B, D, R) -> (G, B, K): the squared distance
    sum_r (o_b - negs_k @ M_b)_r^2 of every triplet to every shared negative
    of its group (kernel ``kge.transr_score``)."""
    return _transr_fwd(o, negs, m)[0]


def _transr_fwd(o, negs, m):
    o_t, negs_p, m_t, kc = _r_major(o, negs, m)
    out = transr_fwd_pallas(o_t, negs_p, m_t, kc=kc, mxu_dtype=_mxu_dtype(),
                            interpret=_interpret())
    return jnp.swapaxes(out[:, : negs.shape[1]], 1, 2), (o, negs, m)


def _transr_bwd(res, g):
    o, negs, m = res
    R, K = o.shape[2], negs.shape[1]
    o_t, negs_p, m_t, kc = _r_major(o, negs, m)
    g_t = _pad(jnp.swapaxes(g.astype(jnp.float32), 1, 2), 1, negs_p.shape[1])
    d_m, d_n, d_o = transr_bwd_pallas(o_t, negs_p, m_t, g_t, kc=kc,
                                      mxu_dtype=_mxu_dtype(), interpret=_interpret())
    d_o = jnp.swapaxes(jnp.sum(d_o, axis=1)[:, :R], 1, 2)
    d_m = jnp.transpose(jnp.sum(d_m, axis=1)[:, :R], (0, 3, 2, 1))
    return d_o.astype(o.dtype), d_n[:, :K].astype(negs.dtype), d_m.astype(m.dtype)


transr_l2sq.defvjp(_transr_fwd, _transr_bwd)
