"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import mha_ref
from repro.kernels.kge_score.ops import pairwise_scores_kernel, transr_l2sq
from repro.kernels.kge_score.ref import pairwise_ref, transr_l2sq_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_chunked_jnp, ssd_ref

RNG = np.random.default_rng(0)


# ------------------------------------------------------------------ kge_score
@pytest.mark.parametrize("mode", ["dot", "l2sq", "l1"])
@pytest.mark.parametrize("shape", [(64, 32, 48), (128, 256, 400), (100, 130, 33),
                                   (8, 8, 8), (1, 1, 1)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_kge_score_sweep(mode, shape, dtype):
    B, K, D = shape
    o = RNG.standard_normal((B, D)).astype(dtype)
    n = RNG.standard_normal((K, D)).astype(dtype)
    out = pairwise_scores_kernel(mode, jnp.asarray(o), jnp.asarray(n))
    ref = pairwise_ref(mode, jnp.asarray(o, jnp.float32), jnp.asarray(n, jnp.float32))
    tol = 2e-5 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("mode", ["dot", "l2sq", "l1"])
def test_kge_score_grads(mode):
    B, K, D = 48, 72, 56
    o = jnp.asarray(RNG.standard_normal((B, D)).astype(np.float32))
    n = jnp.asarray(RNG.standard_normal((K, D)).astype(np.float32))
    g = jnp.asarray(RNG.standard_normal((B, K)).astype(np.float32))
    f = lambda o_, n_: jnp.sum(pairwise_scores_kernel(mode, o_, n_) * g)
    fr = lambda o_, n_: jnp.sum(pairwise_ref(mode, o_, n_) * g)
    do, dn = jax.grad(f, argnums=(0, 1))(o, n)
    dor, dnr = jax.grad(fr, argnums=(0, 1))(o, n)
    np.testing.assert_allclose(do, dor, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(dn, dnr, rtol=2e-4, atol=2e-4)


# (G, B, K, D, R): widths off the 128 lanes, a B over 128 that pads to 256,
# a B under 8, and candidates in two blocks of 512 (1,100 pads to 1,536)
TRANSR_SHAPES = [(2, 16, 24, 20, 12), (1, 5, 7, 9, 11), (2, 136, 8, 8, 16),
                 (1, 9, 1100, 8, 4), (4, 32, 40, 200, 200)]


def _transr_inputs(G, B, K, D, R):
    rng = np.random.default_rng(G * B * K * D * R)
    f = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))  # noqa: E731
    return f(G, B, R), f(G, K, D), 0.3 * f(G, B, D, R), f(G, B, K)


@pytest.mark.parametrize("shape", TRANSR_SHAPES)
def test_transr_score_matches_ref(shape):
    o, n, m, _ = _transr_inputs(*shape)
    np.testing.assert_allclose(transr_l2sq(o, n, m), transr_l2sq_ref(o, n, m),
                               rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("shape", TRANSR_SHAPES)
def test_transr_score_grads_match_ref(shape):
    """The backward kernel's d_o, d_negs and d_m against autodiff of the
    oracle."""
    o, n, m, g = _transr_inputs(*shape)
    got = jax.grad(lambda *a: jnp.sum(transr_l2sq(*a) * g), argnums=(0, 1, 2))(o, n, m)
    want = jax.grad(lambda *a: jnp.sum(transr_l2sq_ref(*a) * g), argnums=(0, 1, 2))(o, n, m)
    for name, x, y in zip(("d_o", "d_negs", "d_m"), got, want):
        scale = float(jnp.max(jnp.abs(y)))
        np.testing.assert_allclose(x, y, rtol=2e-5, atol=2e-5 * scale, err_msg=name)


# ------------------------------------------------------------- flash attention
@pytest.mark.parametrize(
    "B,H,Hkv,T,S,dh,win,qoff",
    [
        (2, 4, 2, 128, 128, 64, 0, 0),
        (1, 8, 8, 64, 256, 32, 0, 192),
        (2, 4, 1, 256, 256, 64, 64, 0),
        (1, 2, 2, 100, 100, 64, 0, 0),
        (1, 4, 2, 1, 512, 64, 0, 511),
        (1, 2, 2, 128, 128, 128, 96, 0),
    ],
)
def test_flash_attention_sweep(B, H, Hkv, T, S, dh, win, qoff):
    q = RNG.standard_normal((B, H, T, dh)).astype(np.float32)
    k = RNG.standard_normal((B, Hkv, S, dh)).astype(np.float32)
    v = RNG.standard_normal((B, Hkv, S, dh)).astype(np.float32)
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=True, window=win, q_offset=qoff, bq=64, bkv=64)
    ref = mha_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                  causal=True, window=win, q_offset=qoff)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    B, H, T, dh = 1, 2, 128, 64
    q = jnp.asarray(RNG.standard_normal((B, H, T, dh)), jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((B, H, T, dh)), jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((B, H, T, dh)), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, bq=64, bkv=64)
    ref = mha_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                  v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(out.astype(jnp.float32), ref, rtol=5e-2, atol=5e-2)


# ------------------------------------------------------------------- ssd scan
@pytest.mark.parametrize("T,H,P,N,chunk", [
    (128, 4, 32, 16, 32), (256, 2, 64, 32, 64), (64, 8, 16, 128, 64),
    (32, 1, 8, 8, 8),
])
def test_ssd_scan_sweep(T, H, P, N, chunk):
    x = RNG.standard_normal((T, H, P)).astype(np.float32)
    dt = ((0.5 + RNG.random((T, H))) * 0.1).astype(np.float32)
    A = (-1.0 - RNG.random(H)).astype(np.float32)
    Bm = (RNG.standard_normal((T, N)) * 0.5).astype(np.float32)
    Cm = (RNG.standard_normal((T, N)) * 0.5).astype(np.float32)
    yr, sr = ssd_ref(x, dt, A, Bm, Cm)
    yc, sc = ssd_chunked_jnp(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(yc, yr, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(sc, sr, rtol=1e-4, atol=1e-4)
    yk = ssd_scan(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(A),
                  jnp.asarray(Bm), jnp.asarray(Cm), chunk=chunk)
    np.testing.assert_allclose(yk, yr, rtol=1e-4, atol=1e-4)


def test_ssd_chunked_with_initial_state():
    T, H, P, N = 64, 2, 16, 8
    x = RNG.standard_normal((T, H, P)).astype(np.float32)
    dt = ((0.5 + RNG.random((T, H))) * 0.1).astype(np.float32)
    A = (-1.0 - RNG.random(H)).astype(np.float32)
    Bm = (RNG.standard_normal((T, N)) * 0.5).astype(np.float32)
    Cm = (RNG.standard_normal((T, N)) * 0.5).astype(np.float32)
    s0 = RNG.standard_normal((H, P, N)).astype(np.float32)
    yr, sr = ssd_ref(x, dt, A, Bm, Cm, init_state=jnp.asarray(s0))
    yc, sc = ssd_chunked_jnp(x, dt, A, Bm, Cm, chunk=16, init_state=jnp.asarray(s0))
    np.testing.assert_allclose(yc, yr, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(sc, sr, rtol=1e-4, atol=1e-4)
