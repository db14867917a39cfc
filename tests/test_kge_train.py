"""Single-machine KGE training: all models learn; kernel path == jnp path."""

import jax
import numpy as np
import pytest

from repro.common.config import KGEConfig
from repro.core.kge_model import (
    batch_to_device, init_state, make_train_step, naive_train_step,
)
from repro.core.sampling import JointSampler, NaiveSampler
from repro.kernels.kge_score.ops import kernel_pairwise_fn

ALL_MODELS = ["transe_l1", "transe_l2", "distmult", "complex", "rotate",
              "transr", "rescal"]


def _cfg(kg, model, **kw):
    base = dict(model=model, n_entities=kg.n_entities,
                n_relations=kg.n_relations, dim=32,
                rel_dim=16 if model == "transr" else 0,
                batch_size=128, neg_sample_size=64, lr=0.1, n_parts=1)
    base.update(kw)
    return KGEConfig(**base)


@pytest.mark.parametrize("model", ALL_MODELS)
def test_all_models_learn(small_kg, model):
    cfg = _cfg(small_kg, model)
    state = init_state(cfg, jax.random.key(0))
    step = make_train_step(cfg)
    sampler = JointSampler(small_kg.train, cfg.n_entities, cfg,
                           np.random.default_rng(0))
    losses = []
    for _ in range(25):
        state, m = step(state, batch_to_device(sampler.sample()))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("model", ["transe_l1", "transe_l2", "distmult", "rotate"])
def test_kernel_path_matches_jnp(small_kg, model):
    """Pallas kge_score is a drop-in for the jnp pairwise path."""
    cfg = _cfg(small_kg, model)
    sampler = JointSampler(small_kg.train, cfg.n_entities, cfg,
                           np.random.default_rng(0))
    batches = [batch_to_device(sampler.sample()) for _ in range(5)]

    def run(pairwise_fn):
        state = init_state(cfg, jax.random.key(0))
        step = make_train_step(cfg, pairwise_fn)
        out = []
        for b in batches:
            state, m = step(state, b)
            out.append(float(m["loss"]))
        return np.asarray(out), state

    l_ref, s_ref = run(None)
    l_k, s_k = run(kernel_pairwise_fn)
    np.testing.assert_allclose(l_k, l_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s_k.entity, s_ref.entity, rtol=2e-3, atol=2e-4)


def test_transr_kernel_path_matches_einsum(small_kg, monkeypatch):
    """With the platform read as a TPU, TransR's joint negatives go through
    the kge.transr_score kernels (interpret mode here), each mode's groups
    on the kernel's grid, and train as the einsum path does."""
    import types

    from repro.common import compat
    from repro.core import scores

    cfg = _cfg(small_kg, "transr", neg_group_size=32)
    sampler = JointSampler(small_kg.train, cfg.n_entities, cfg,
                           np.random.default_rng(0))
    batches = [batch_to_device(sampler.sample()) for _ in range(4)]

    def run():
        state = init_state(cfg, jax.random.key(0), overlap=True)
        step = make_train_step(cfg)
        losses = []
        for b in batches:
            state, m = step(state, b)
            losses.append(float(m["loss"]))
        return np.asarray(losses), state, step

    l_ref, s_ref, step_ref = run()
    monkeypatch.setattr(scores, "compat", types.SimpleNamespace(
        backend=lambda: "tpu", axis_size=compat.axis_size))
    l_k, s_k, step_k = run()
    batch = batches[0]
    state = init_state(cfg, jax.random.key(0), overlap=True)
    assert "transr_score" in str(jax.make_jaxpr(step_k)(state, batch))
    assert "transr_score" not in str(jax.make_jaxpr(step_ref)(state, batch))
    np.testing.assert_allclose(l_k, l_ref, rtol=1e-5)
    for tab in ("entity", "r_emb", "r_proj"):
        a, b = np.asarray(getattr(s_k, tab)), np.asarray(getattr(s_ref, tab))
        assert np.linalg.norm(a - b) <= 1e-4 * np.linalg.norm(b), tab


def test_naive_baseline_also_learns(small_kg):
    cfg = _cfg(small_kg, "transe_l2")
    state = init_state(cfg, jax.random.key(0))
    sampler = NaiveSampler(small_kg.train, cfg.n_entities, cfg,
                           np.random.default_rng(0))
    import functools

    import jax.numpy as jnp

    step = jax.jit(functools.partial(naive_train_step, cfg))
    losses = []
    for _ in range(20):
        b = sampler.sample()
        batch = {k: jnp.asarray(getattr(b, k), jnp.int32)
                 for k in ("h", "r", "t", "neg")}
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_only_touched_rows_change(small_kg):
    """Sparse updates: untouched entity rows must be bit-identical."""
    cfg = _cfg(small_kg, "transe_l2", batch_size=16, neg_sample_size=8)
    state = init_state(cfg, jax.random.key(0))
    step = make_train_step(cfg)
    sampler = JointSampler(small_kg.train, cfg.n_entities, cfg,
                           np.random.default_rng(0))
    b = sampler.sample()
    touched = set(np.concatenate([b.h, b.t, b.neg.reshape(-1)]).tolist())
    before = np.asarray(state.entity)
    state2, _ = step(state, batch_to_device(b))
    after = np.asarray(state2.entity)
    untouched = np.setdiff1d(np.arange(cfg.n_entities), list(touched))
    np.testing.assert_array_equal(before[untouched], after[untouched])
    changed = np.abs(after[list(touched)] - before[list(touched)]).sum(axis=1)
    assert (changed > 0).mean() > 0.9  # almost all touched rows moved


def test_overlap_single_machine(small_kg):
    """T5 on the single-machine path: deferred updates train, and a deferred
    step followed by flush equals the immediate step exactly."""
    import jax.numpy as jnp

    from repro.core.kge_model import flush_state, train_step

    cfg = _cfg(small_kg, "transe_l2")
    sampler = JointSampler(small_kg.train, cfg.n_entities, cfg,
                           np.random.default_rng(0))
    batches = [batch_to_device(sampler.sample()) for _ in range(12)]

    state = init_state(cfg, jax.random.key(0), overlap=True)
    step = make_train_step(cfg)
    losses = []
    for b in batches:
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    # pending grads exist mid-training; flush applies and clears them
    assert bool(jnp.any(state.pend_ids >= 0))
    flushed = flush_state(cfg, state)
    np.testing.assert_array_equal(np.asarray(flushed.pend_ids), -1)
    assert np.abs(np.asarray(flushed.entity - state.entity)).sum() > 0

    # single step: defer + flush == immediate
    s0_ov = init_state(cfg, jax.random.key(1), overlap=True)
    s0_im = init_state(cfg, jax.random.key(1), overlap=False)
    np.testing.assert_array_equal(np.asarray(s0_ov.entity),
                                  np.asarray(s0_im.entity))
    s1_ov, _ = train_step(cfg, s0_ov, batches[0])
    s1_im, _ = train_step(cfg, s0_im, batches[0])
    np.testing.assert_allclose(np.asarray(flush_state(cfg, s1_ov).entity),
                               np.asarray(s1_im.entity), rtol=1e-6, atol=1e-7)


def _dict_batch(b):
    """The four-field device form of a sampled batch (the dict
    ``dense_step_batch`` also takes)."""
    import jax.numpy as jnp

    return {k: jnp.asarray(getattr(b, k), jnp.int32) for k in ("h", "r", "t", "neg")}


@pytest.mark.parametrize("path,overlap", [("train", False), ("train", True),
                                          ("hogwild", False), ("naive", False)])
def test_packed_batch_trains_bitwise_as_dict(small_kg, path, overlap):
    """The one-transfer ``PackedBatch`` and the dict of the same batches give
    the same loss and state, bit for bit, after three steps, through the
    one-shot step (T5 on and off), Hogwild's grad and apply pair and the
    naive-negative step."""
    import functools

    from repro.core.kge_model import PackedBatch, make_hogwild_step

    cfg = _cfg(small_kg, "transe_l2")
    sampler = (NaiveSampler if path == "naive" else JointSampler)(
        small_kg.train, cfg.n_entities, cfg, np.random.default_rng(0))
    sampled = [sampler.sample() for _ in range(3)]

    def run(to_dev):
        state = init_state(cfg, jax.random.key(0), overlap=overlap)
        if path == "train":
            step = make_train_step(cfg)
        elif path == "naive":
            step = jax.jit(functools.partial(naive_train_step, cfg))
        else:
            g, a = make_hogwild_step(cfg)

            def step(st, batch):
                grads, m = g(st, batch)
                return a(st, batch, grads), m
        losses = []
        for b in sampled:
            state, m = step(state, to_dev(b))
            losses.append(np.asarray(m["loss"]))
        return losses, state

    packed = batch_to_device(sampled[0])
    assert isinstance(packed, PackedBatch) and len(jax.tree.leaves(packed)) == 1
    l_p, s_p = run(batch_to_device)
    l_d, s_d = run(_dict_batch)
    np.testing.assert_array_equal(np.stack(l_p), np.stack(l_d))
    for a, b in zip(jax.tree.leaves(s_p), jax.tree.leaves(s_d), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dense_step_batch_same_for_packed_and_dict(small_kg):
    """Both batch forms lower to the same workspace ids and slots."""
    from repro.core.kge_model import dense_step_batch

    cfg = _cfg(small_kg, "transe_l2")
    b = JointSampler(small_kg.train, cfg.n_entities, cfg,
                     np.random.default_rng(0)).sample()
    packed = dense_step_batch(batch_to_device(b))
    plain = dense_step_batch(_dict_batch(b))
    assert packed.keys() == plain.keys()
    for k in plain:
        assert packed[k].dtype == plain[k].dtype, k
        np.testing.assert_array_equal(np.asarray(packed[k]), np.asarray(plain[k]))
    np.testing.assert_array_equal(
        np.asarray(packed["ent_ids"]),
        np.concatenate([b.h, b.t, b.neg.reshape(-1)]))


def test_self_adversarial_loss(small_kg):
    """RotatE with self-adversarial negative weighting (the RotatE-codebase
    option DGL-KE inherits) trains stably and weights hard negatives."""
    import jax.numpy as jnp

    from repro.core.losses import self_adversarial_loss

    cfg = _cfg(small_kg, "rotate", loss="self_adv")
    state = init_state(cfg, jax.random.key(0))
    step = make_train_step(cfg)
    sampler = JointSampler(small_kg.train, cfg.n_entities, cfg,
                           np.random.default_rng(0))
    losses = []
    for _ in range(15):
        state, m = step(state, batch_to_device(sampler.sample()))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    # weighting property: a high-scoring negative contributes more
    pos = jnp.asarray([1.0])
    neg_easy = jnp.asarray([[-10.0, -10.0]])
    neg_hard = jnp.asarray([[5.0, -10.0]])
    assert float(self_adversarial_loss(pos, neg_hard)) > float(
        self_adversarial_loss(pos, neg_easy))


def test_launcher_builds_transr_at_the_given_dim(monkeypatch):
    """``--model transr --dim 200`` trains rel_dim 200 (DGL-KE's
    relation_dim = hidden_dim): 200-wide relations, 200x200 projections."""
    from repro.common import compile_cache
    from repro.launch.train import main

    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "")
    state = main(["--model", "transr", "--dim", "200", "--steps", "2",
                  "--scale", "0.01", "--batch-size", "16", "--neg", "8",
                  "--log-every", "1"])
    assert state.entity.shape[1] == 200
    assert state.r_emb.shape[1] == 200
    assert state.r_proj.shape[1] == 200 * 200
