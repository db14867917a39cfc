"""Sparse Adagrad (DGL-KE's optimizer) + dense optimizers."""

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.optim.dense import adafactor, adamw, sgd
from repro.optim.sparse_adagrad import (
    dense_adagrad_update, segment_aggregate_rows,
    sparse_adagrad_init, sparse_adagrad_update_rows,
)


def test_sparse_matches_dense_when_full():
    rng = np.random.default_rng(0)
    n, d = 16, 8
    table = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    grad = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    st0 = sparse_adagrad_init(table)
    dt, dstate = dense_adagrad_update(table, st0, grad, lr=0.1)
    st1 = sparse_adagrad_init(table)
    stab, sstate = sparse_adagrad_update_rows(
        table, st1, jnp.arange(n, dtype=jnp.int32), grad, lr=0.1)
    np.testing.assert_allclose(stab, dt, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(sstate.gsq, dstate.gsq, rtol=1e-6)


def test_padding_rows_are_noops():
    table = jnp.ones((4, 3))
    state = sparse_adagrad_init(table)
    ids = jnp.array([-1, 2, -1], jnp.int32)
    grads = jnp.ones((3, 3))
    new, st2 = sparse_adagrad_update_rows(table, state, ids, grads, lr=0.5)
    np.testing.assert_allclose(new[0], table[0])
    np.testing.assert_allclose(new[1], table[1])
    assert not np.allclose(new[2], table[2])
    assert (np.asarray(st2.gsq[0]) == 0).all()


@settings(max_examples=30, deadline=None)
@given(
    n_ids=st.integers(1, 40),
    n_rows=st.integers(1, 12),
    d=st.integers(1, 6),
    seed=st.integers(0, 10),
)
def test_segment_aggregate_property(n_ids, n_rows, d, seed):
    rng = np.random.default_rng(seed)
    ids = rng.integers(-1, n_rows, size=n_ids).astype(np.int32)
    grads = rng.standard_normal((n_ids, d)).astype(np.float32)
    uid, agg = segment_aggregate_rows(jnp.asarray(ids), jnp.asarray(grads))
    uid, agg = np.asarray(uid), np.asarray(agg)
    # reference aggregation
    want = {}
    for i, g in zip(ids, grads):
        if i >= 0:
            want[i] = want.get(i, 0) + g
    got = {int(u): agg[j] for j, u in enumerate(uid) if u >= 0}
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-4, atol=1e-5)


def test_duplicate_ids_aggregate_before_adagrad():
    """Applying duplicate ids must equal aggregating first (Adagrad is
    nonlinear — this is why the pipeline dedups)."""
    table = jnp.zeros((3, 2))
    state = sparse_adagrad_init(table)
    ids = jnp.array([1, 1], jnp.int32)
    grads = jnp.array([[1.0, 1.0], [1.0, 1.0]])
    uid, agg = segment_aggregate_rows(ids, grads)
    new, _ = sparse_adagrad_update_rows(table, state, uid, agg, lr=1.0)
    # aggregated grad = 2 -> step = 2/sqrt(4) = 1
    np.testing.assert_allclose(new[1], [-1.0, -1.0], rtol=1e-5)


def _quad_min(opt, steps=800):
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)
    for _ in range(steps):
        g = {"w": 2 * (params["w"] - target)}
        params, state = opt.update(params, g, state)
    return float(jnp.max(jnp.abs(params["w"] - target)))


def test_dense_optimizers_converge():
    assert _quad_min(sgd(0.1)) < 1e-3
    assert _quad_min(adamw(0.05)) < 1e-2
    assert _quad_min(adafactor(0.1), steps=2000) < 1e-1


def test_adafactor_state_is_factored():
    opt = adafactor(0.01)
    params = {"w": jnp.zeros((64, 32)), "b": jnp.zeros(32)}
    state = opt.init(params)
    assert state["stats"]["w"]["vr"].shape == (64,)
    assert state["stats"]["w"]["vc"].shape == (32,)
    assert state["stats"]["b"]["v"].shape == (32,)


@pytest.mark.parametrize("width", [5, 16, 40])
def test_add_rows_matches_one_scatter(width, monkeypatch):
    """add_rows in column blocks (here of 16) sums duplicate and dropped ids
    as one scatter-add of the whole rows does."""
    from repro.optim import sparse_adagrad as SA

    monkeypatch.setattr(SA, "ROW_CHUNK", 16)
    rng = np.random.default_rng(width)
    # repeats, and ids 8 and 99 out of range (dropped)
    idx = jnp.asarray(rng.integers(-1, 9, 30), jnp.int32)
    idx = jnp.where(idx < 0, 99, idx)
    rows = jnp.asarray(rng.standard_normal((30, width)), jnp.float32)
    want = jnp.zeros((8, width), jnp.float32).at[idx].add(rows, mode="drop")
    np.testing.assert_allclose(SA.add_rows(8, idx, rows), want, rtol=1e-6, atol=1e-6)
