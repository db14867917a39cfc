"""Runs the program's train step and the float32 step reference
(``_step_reference.py``) side by side on the same host batches, and
compares each step's loss and every table and ``gsq``.

One case is (path, model, loss), three steps at a tiny shape: 64 entities,
8 relations, 600 edges, d 16 (TransR's rel_dim 8), b 16 in two groups of 8
sharing k 8 negatives a mode. The paths:

* ``immediate``: ``make_train_step`` on ``init_state(overlap=False)``;
* ``deferred``: the same with ``overlap=True`` (T5): after each step the
  entity table lacks that step's update; compared again after
  ``flush_state``;
* ``hogwild``: ``make_hogwild_step``, ``grad_step`` then ``apply_step``;
* ``naive``: ``naive_train_step`` under ``jax.jit``, (2, b, k) negatives.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from _step_reference import StepReference
from repro.common.config import KGEConfig
from repro.core import kge_model as K
from repro.core.sampling import JointSampler, NaiveSampler
from repro.data.kg_synth import make_synthetic_kg

LOSSES = ("logistic", "ranking", "self_adv")
STEPS = 3
# The loss: relative gap. Tables and gsq: largest absolute gap over the
# table's largest magnitude. Float32 sums taken in another order put both
# under 1e-6 here; a fault moves a table by about lr = 0.1.
LOSS_RTOL = 1e-5
TABLE_TOL = 2e-5
# a first update is lr * sign(g): no such component may sit within
# rounding (~1e-7 of the largest) of zero
FIRST_TOUCH_MIN = 1e-6


@functools.lru_cache(maxsize=None)
def _graph():
    return make_synthetic_kg(n_entities=64, n_relations=8, n_edges=600, seed=0)


def case_config(model: str, loss: str) -> KGEConfig:
    return KGEConfig(model=model, loss=loss, n_entities=64, n_relations=8,
                     dim=16, rel_dim=8 if model == "transr" else 0,
                     batch_size=16, neg_sample_size=8, neg_group_size=8,
                     neg_deg_ratio=0.5, lr=0.1)


# table name -> (KGEState field of the table, of its gsq)
FIELDS = {"entity": ("entity", "ent_gsq"), "rel": ("r_emb", "rel_gsq"),
          "proj": ("r_proj", "proj_gsq")}


def _host(state, which: int):
    """The state's tables (``which`` 0) or their gsq (1) as host arrays."""
    arrays = {name: getattr(state, f[which]) for name, f in FIELDS.items()}
    return {name: np.asarray(a) for name, a in arrays.items() if a is not None}


def _assert_close(what: str, got: np.ndarray, want: np.ndarray) -> None:
    scale = max(float(np.max(np.abs(want))), 1e-30)
    gap = float(np.max(np.abs(got - want))) / scale
    assert gap <= TABLE_TOL, f"{what}: gap {gap:.3g} of {scale:.3g} > {TABLE_TOL}"


def _assert_state(step: int, state, ref: StepReference) -> None:
    for name, got in _host(state, 0).items():
        _assert_close(f"step {step} {name}", got, ref.tables[name])
    for name, got in _host(state, 1).items():
        _assert_close(f"step {step} {name} gsq", got, ref.gsq[name])


def run_case(path: str, model: str, loss: str) -> None:
    cfg = case_config(model, loss)
    kg = _graph()
    naive = path == "naive"
    deferred = path == "deferred"
    sampler = (NaiveSampler if naive else JointSampler)(
        kg.train, cfg.n_entities, cfg, np.random.default_rng(0))
    state = K.init_state(cfg, jax.random.key(0), overlap=deferred)
    ref = StepReference(model, loss, gamma=cfg.gamma, lr=cfg.lr,
                        rel_dim=cfg.rel_dim, tables=_host(state, 0),
                        deferred=deferred)

    if path == "hogwild":
        grad_fn, apply_fn = K.make_hogwild_step(cfg)

        def step(st, pb):
            grads, metrics = grad_fn(st, pb)
            return apply_fn(st, pb, grads), metrics
    elif naive:
        step = jax.jit(functools.partial(K.naive_train_step, cfg))
    else:
        step = K.make_train_step(cfg)

    for i in range(1, STEPS + 1):
        batch = sampler.sample()
        state, metrics = step(state, K.batch_to_device(batch))
        want = ref.step(batch.h, batch.r, batch.t, batch.neg)
        assert ref.min_first_touch >= FIRST_TOUCH_MIN, (
            f"step {i}: a first update's gradient is {ref.min_first_touch:.3g}"
            " of the largest, too close to zero for this seed to compare")
        got = float(metrics["loss"])
        assert abs(got - want) <= LOSS_RTOL * abs(want), (
            f"step {i} loss {got!r} != reference {want!r}")
        _assert_state(i, state, ref)
    if deferred:
        state = K.flush_state(cfg, state)
        ref.flush()
        _assert_state(STEPS + 1, state, ref)
