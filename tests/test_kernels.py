"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.kge_score.ops import pairwise_scores_kernel, transr_l2sq
from repro.kernels.kge_score.ref import pairwise_ref, transr_l2sq_ref

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

