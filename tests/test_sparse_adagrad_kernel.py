"""Fused sparse-Adagrad Pallas kernels vs jnp references (interpret mode).

Layers covered, bottom-up:
  * kernels/sparse_adagrad ops vs ref.py oracles (dtypes, pads), and raw
    duplicated ids vs segment dedup followed by the oracle;
  * optim.sparse_adagrad_apply kernel-vs-jnp path parity;
  * optim.dedup_compact_rows capacity bound + overflow accounting;
  * store_train_step numerics with the kernel enabled on all three stores
    (incl. the Dense↔Sharded n_parts==1 parity invariant);
  * a Hogwild smoke run with use_kernel=True.

All Pallas calls run the interpret-mode emulator on CPU (compat auto-detects);
on a real TPU the same tests exercise the compiled kernels.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.sparse_adagrad import fused_sparse_adagrad
from repro.kernels.sparse_adagrad.ref import fused_update_ref
from repro.optim.sparse_adagrad import (
    dedup_compact_rows, segment_aggregate_rows, set_use_kernel,
    sparse_adagrad_apply, use_kernel,
)


@pytest.fixture
def kernel_on():
    set_use_kernel(True)
    yield
    set_use_kernel(None)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype in (jnp.float16, jnp.bfloat16) \
        else dict(rtol=2e-5, atol=2e-6)


def _mk(rng, N, D, n, dtype=jnp.float32, frac_pad=0.2):
    table = jnp.asarray(rng.standard_normal((N, D)), dtype)
    gsq = jnp.asarray(np.abs(rng.standard_normal((N, D))), dtype)
    # unique valid ids with pads interleaved
    perm = rng.permutation(N)[:n]
    ids = np.where(rng.random(n) < frac_pad, -1, perm).astype(np.int32)
    grads = jnp.asarray(rng.standard_normal((n, D)), dtype)
    return table, gsq, jnp.asarray(ids), grads


# ---------------------------------------------------------------------------
# fused update kernel vs oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float16, jnp.bfloat16])
def test_fused_update_matches_ref_dtypes(dtype):
    rng = np.random.default_rng(0)
    table, gsq, ids, grads = _mk(rng, 64, 32, 20, dtype)
    t_k, q_k = fused_sparse_adagrad(table, gsq, ids, grads, 0.1)
    t_r, q_r = fused_update_ref(table, gsq, ids, grads, 0.1)
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(t_k, np.float32),
                               np.asarray(t_r, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(q_k, np.float32),
                               np.asarray(q_r, np.float32), **tol)


@pytest.mark.parametrize("ids_np", [
    [-1, -1, 3, -1, 7, -1, -1, 5],   # leading + interleaved + trailing pads
    [-1, -1, -1, -1],                # all pads: bitwise no-op
    [2],                             # single row
])
def test_fused_update_pad_rows_are_noops(ids_np):
    rng = np.random.default_rng(1)
    N, D = 16, 24
    table = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    gsq = jnp.asarray(np.abs(rng.standard_normal((N, D))), jnp.float32)
    ids = jnp.asarray(ids_np, jnp.int32)
    grads = jnp.asarray(rng.standard_normal((len(ids_np), D)), jnp.float32)
    t_k, q_k = fused_sparse_adagrad(table, gsq, ids, grads, 0.2)
    t_r, q_r = fused_update_ref(table, gsq, ids, grads, 0.2)
    np.testing.assert_allclose(np.asarray(t_k), np.asarray(t_r),
                               rtol=2e-5, atol=2e-6)
    # untouched rows must be BIT-identical (in-place alias, never copied)
    touched = {i for i in ids_np if i >= 0}
    untouched = sorted(set(range(N)) - touched)
    np.testing.assert_array_equal(np.asarray(t_k)[untouched],
                                  np.asarray(table)[untouched])
    np.testing.assert_array_equal(np.asarray(q_k)[untouched],
                                  np.asarray(gsq)[untouched])


def test_fused_update_d_tiling():
    """D divisible by a tile (256) exercises the multi-column d-outer grid."""
    rng = np.random.default_rng(2)
    table, gsq, ids, grads = _mk(rng, 32, 256, 12)
    t_k, q_k = fused_sparse_adagrad(table, gsq, ids, grads, 0.05)
    t_r, q_r = fused_update_ref(table, gsq, ids, grads, 0.05)
    np.testing.assert_allclose(np.asarray(t_k), np.asarray(t_r),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(q_k), np.asarray(q_r),
                               rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# duplicate ids: summed in the tile grouping's scatter-add
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,D,case", [
    (7, 5, "dups"), (33, 40, "dups"), (64, 128, "dups"), (512, 400, "dups"),
    (33, 40, "all_same"), (64, 128, "bf16"),
])
def test_fused_update_aggregates_duplicates(n, D, case):
    """Raw duplicated ids through the fused path == segment-dedup followed
    by the fused-update oracle."""
    rng = np.random.default_rng(3)
    N = max(10, n // 2)  # many duplicates; few tiles keep interpret mode fast
    dtype = jnp.bfloat16 if case == "bf16" else jnp.float32
    table = jnp.asarray(rng.standard_normal((N, D)), dtype)
    gsq = jnp.asarray(np.abs(rng.standard_normal((N, D))), dtype)
    ids_np = (np.full(n, 5) if case == "all_same"
              else rng.integers(-1, N, size=n))
    ids = jnp.asarray(ids_np, jnp.int32)
    grads = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    t_k, q_k = fused_sparse_adagrad(table, gsq, ids, grads, 0.1)
    uid, agg = segment_aggregate_rows(ids, grads)
    t_r, q_r = fused_update_ref(table, gsq, uid, agg, 0.1)
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(t_k, np.float32),
                               np.asarray(t_r, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(q_k, np.float32),
                               np.asarray(q_r, np.float32), **tol)
    untouched = sorted(set(range(N)) - {int(i) for i in ids_np if i >= 0})
    np.testing.assert_array_equal(np.asarray(t_k)[untouched],
                                  np.asarray(table)[untouched])


def test_dedup_then_fused_equals_apply_with_duplicates():
    """Raw duplicated ids straight into the fused path == the jnp
    sparse_adagrad_apply (segment dedup, then row updates)."""
    rng = np.random.default_rng(4)
    N, D, n = 20, 16, 30
    table = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    gsq = jnp.asarray(np.abs(rng.standard_normal((N, D))), jnp.float32)
    ids = jnp.asarray(rng.integers(-1, N, size=n), jnp.int32)
    grads = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    t_k, q_k = fused_sparse_adagrad(table, gsq, ids, grads, 0.1)
    t_j, q_j = sparse_adagrad_apply(table, gsq, ids, grads, 0.1,
                                    use_kernel=False)
    np.testing.assert_allclose(np.asarray(t_k), np.asarray(t_j),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(q_k), np.asarray(q_j),
                               rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# optim dispatch layer
# ---------------------------------------------------------------------------
def test_apply_kernel_path_matches_jnp_path():
    rng = np.random.default_rng(5)
    N, D, n = 50, 24, 40
    table = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    gsq = jnp.asarray(np.abs(rng.standard_normal((N, D))), jnp.float32)
    ids = jnp.asarray(rng.integers(-1, N, size=n), jnp.int32)
    grads = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    t_j, q_j = sparse_adagrad_apply(table, gsq, ids, grads, 0.1,
                                    use_kernel=False)
    t_k, q_k = sparse_adagrad_apply(table, gsq, ids, grads, 0.1,
                                    use_kernel=True)
    np.testing.assert_allclose(np.asarray(t_k), np.asarray(t_j),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(q_k), np.asarray(q_j),
                               rtol=2e-5, atol=2e-6)


def test_use_kernel_override_and_env(monkeypatch):
    set_use_kernel(True)
    assert use_kernel() is True
    set_use_kernel(False)
    assert use_kernel() is False
    set_use_kernel(None)
    monkeypatch.setenv("REPRO_SPARSE_ADAGRAD_KERNEL", "1")
    assert use_kernel() is True
    monkeypatch.setenv("REPRO_SPARSE_ADAGRAD_KERNEL", "0")
    assert use_kernel() is False


@pytest.mark.parametrize("cap", [6, 8])
def test_dedup_compact_rows_bounds_capacity(cap):
    rng = np.random.default_rng(6)
    n, D = 24, 8
    ids = jnp.asarray(rng.integers(0, 6, size=n), jnp.int32)  # ≤6 uniques
    grads = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    cids, cgrads, dropped = dedup_compact_rows(ids, grads, cap)
    assert cids.shape == (cap,) and cgrads.shape == (cap, D)
    assert int(dropped) == 0
    got = {int(i): np.asarray(g) for i, g in zip(cids, cgrads) if i >= 0}
    want = {}
    for i, g in zip(np.asarray(ids), np.asarray(grads)):
        want[int(i)] = want.get(int(i), 0) + g
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-6)


def test_dedup_compact_rows_counts_overflow():
    ids = jnp.arange(10, dtype=jnp.int32)  # 10 uniques, capacity 4
    grads = jnp.ones((10, 3), jnp.float32)
    cids, _, dropped = dedup_compact_rows(ids, grads, 4)
    assert int((cids >= 0).sum()) == 4
    assert int(dropped) == 6


# ---------------------------------------------------------------------------
# store level with the kernel enabled
# ---------------------------------------------------------------------------
from repro.common.config import KGEConfig  # noqa: E402
from repro.core.kge_model import (  # noqa: E402
    batch_to_device, dense_step_batch, init_state, make_hogwild_step,
    make_train_step, stores_from_state,
)
from repro.core.sampling import JointSampler  # noqa: E402
from repro.core.step import store_train_step  # noqa: E402
from repro.data.kg_synth import make_synthetic_kg  # noqa: E402
from repro.embeddings.kvstore import KVStoreSpec  # noqa: E402
from repro.embeddings.store import (  # noqa: E402
    DenseStore, ReplicatedStore, ShardedIds, ShardedStore,
)
from repro.launch.engine import MetricsHook, train_loop  # noqa: E402


def _small_cfg(kg, **kw):
    base = dict(model="transe_l2", n_entities=kg.n_entities,
                n_relations=kg.n_relations, dim=16, batch_size=8,
                neg_sample_size=8, lr=0.1, n_parts=1)
    base.update(kw)
    return KGEConfig(**base)


def _small_kg():
    return make_synthetic_kg(n_entities=120, n_relations=8, n_edges=1500,
                             n_clusters=4, seed=0)


def _batches(kg, cfg, n, seed=0):
    sampler = JointSampler(kg.train, cfg.n_entities, cfg,
                           np.random.default_rng(seed))
    return [dense_step_batch(batch_to_device(sampler.sample()))
            for _ in range(n)]


def test_store_train_step_kernel_matches_jnp_all_stores(kernel_on):
    """Acceptance: with use_kernel on, store_train_step numerics match the
    jnp path to fp32 tolerance on Dense, Sharded and Replicated stores."""
    kg = _small_kg()
    cfg = _small_cfg(kg)
    state = init_state(cfg, jax.random.key(0))
    batches = _batches(kg, cfg, 2)
    spec = KVStoreSpec(machine_axis=None, n_parts=1, remote_capacity=1)
    pad = jnp.full((1, 1), -1, jnp.int32)

    def run():
        dense = stores_from_state(cfg, state)
        sharded = {
            "entity": ShardedStore.create(state.entity, spec, cfg.lr),
            "rel": ShardedStore.create(state.r_emb, spec, cfg.lr),
        }
        repl = {
            "entity": DenseStore.create(state.entity, cfg.lr),
            "rel": ReplicatedStore.create(state.r_emb, cfg.lr),
        }
        for db in batches:
            sb = dict(db)
            sb["ent_ids"] = ShardedIds(db["ent_ids"], pad)
            sb["rel_ids"] = ShardedIds(db["rel_ids"], pad)
            dense, _ = store_train_step(cfg, dense, db)
            sharded, _ = store_train_step(cfg, sharded, sb)
            repl, _ = store_train_step(cfg, repl, db)
        return dense, sharded, repl

    k_dense, k_sharded, k_repl = run()
    set_use_kernel(False)
    j_dense, j_sharded, j_repl = run()

    for kst, jst in ((k_dense, j_dense), (k_sharded, j_sharded),
                     (k_repl, j_repl)):
        for name in ("entity", "rel"):
            np.testing.assert_allclose(np.asarray(kst[name].table),
                                       np.asarray(jst[name].table),
                                       rtol=2e-5, atol=2e-6)
            np.testing.assert_allclose(np.asarray(kst[name].gsq),
                                       np.asarray(jst[name].gsq),
                                       rtol=2e-5, atol=2e-6)
    # and the Dense↔Sharded invariant holds WITH the kernel on
    np.testing.assert_allclose(np.asarray(k_sharded["entity"].table),
                               np.asarray(k_dense["entity"].table),
                               rtol=2e-5, atol=2e-6)


def test_capacity_bounded_defer_matches_full_buffer(kernel_on):
    """A pend buffer smaller than the workspace (dedup-before-defer) must
    produce the same flushed table as a workspace-sized buffer, as long as
    the unique count fits."""
    kg = _small_kg()
    cfg = _small_cfg(kg)
    state = init_state(cfg, jax.random.key(1))
    db = _batches(kg, cfg, 1, seed=1)[0]
    n_ws = db["ent_ids"].shape[0]
    n_unique = len({int(i) for i in np.asarray(db["ent_ids"]) if i >= 0})
    cap = n_unique + 4
    assert cap < n_ws, "fixture must actually shrink the buffer"

    def run(slots):
        stores = stores_from_state(cfg, state)
        stores["entity"] = DenseStore.create(state.entity, cfg.lr,
                                             defer=True, pend_slots=slots)
        stores, _ = store_train_step(cfg, stores, db)
        return stores["entity"].flush()

    full = run(n_ws)
    bounded = run(cap)
    assert bounded.pend_ids.shape == (cap,)
    np.testing.assert_allclose(np.asarray(bounded.table),
                               np.asarray(full.table), rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(bounded.gsq),
                               np.asarray(full.gsq), rtol=2e-5, atol=2e-6)


def test_hogwild_smoke_with_kernel(kernel_on):
    """2-trainer Hogwild over the kernel-enabled stores runs and learns."""
    kg = _small_kg()
    cfg = _small_cfg(kg, dim=8, batch_size=8, neg_sample_size=4)
    sampler = JointSampler(kg.train, cfg.n_entities, cfg,
                           np.random.default_rng(0))
    mh = MetricsHook()
    state = train_loop(
        make_train_step(cfg), init_state(cfg, jax.random.key(0)),
        lambda: (batch_to_device(sampler.sample()), None), 10,
        hooks=[mh], n_trainers=2, split_step=make_hogwild_step(cfg))
    assert int(state.step) == 10
    losses = mh.history["loss"]
    assert len(losses) == 10 and all(np.isfinite(losses))
