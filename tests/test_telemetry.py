"""Telemetry stack: registry thread-safety, trace schema, TelemetryHook
JSONL output, Hogwild per-trainer tracks, pend-overflow surfacing, and the
train path's host spans (profiler annotations while tracing) and device
scopes."""

import ast
import json
import sys
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.common import telemetry
from repro.common.telemetry import (
    MetricsRegistry, validate_metrics_jsonl, validate_trace,
)
from repro.embeddings.store import DenseStore
from repro.launch.engine import (
    Hook, LoggingHook, MetricsHook, TelemetryHook, train_loop,
)


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------
def test_registry_counters_exact_under_contention():
    reg = MetricsRegistry(enabled=True)
    n_threads, n_incs = 8, 2000

    def worker():
        for _ in range(n_incs):
            reg.inc("pipeline/produced")
            reg.observe("runtime/staleness", 1.0)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counters["pipeline/produced"] == n_threads * n_incs
    snap = reg.snapshot()
    h = snap["hists"]["runtime/staleness"]
    assert h["count"] == n_threads * n_incs
    assert h["mean"] == 1.0


def test_disabled_registry_records_nothing():
    reg = MetricsRegistry(enabled=False)
    reg.inc("pipeline/produced")
    reg.gauge("pipeline/queue_depth", 3)
    reg.observe("runtime/staleness", 1.0)
    reg.trace_inc("kvstore/pull_rows", 64)
    assert reg.counters == {} and reg.gauges == {}
    assert reg.snapshot()["hists"] == {}
    assert reg.drain_statics() == {}
    # disabled spans are the shared no-op singleton — no per-call allocation
    assert reg.span("x") is reg.span("y") is telemetry._NULL_SPAN


def test_module_helpers_default_disabled_and_active_restores():
    assert not telemetry.enabled()
    telemetry.inc("pipeline/produced")  # no-op, must not raise
    with telemetry.active() as reg:
        assert telemetry.enabled()
        telemetry.inc("pipeline/produced")
        assert reg.counters["pipeline/produced"] == 1
    assert not telemetry.enabled()


def test_trace_inc_buffers_until_drained():
    reg = MetricsRegistry(enabled=True)
    reg.trace_inc("kvstore/pull_rows", 64)
    reg.trace_inc("kvstore/pull_rows", 64)
    assert "kvstore/pull_rows" not in reg.counters  # buffered, not recorded
    assert reg.drain_statics() == {"kvstore/pull_rows": 128.0}
    assert reg.drain_statics() == {}


def test_span_trace_roundtrip(tmp_path):
    reg = MetricsRegistry(enabled=True, trace=True)
    reg.set_track_name("trainer-0")
    with reg.span("runtime/grad"):
        pass
    with reg.span("runtime/apply"):
        pass
    path = tmp_path / "t.json"
    reg.write_trace(str(path))
    assert validate_trace(str(path)) >= 3  # 2 spans + 1 track metadata
    doc = json.loads(path.read_text())
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert names == {"runtime/grad", "runtime/apply"}
    tracks = {e["args"]["name"] for e in doc["traceEvents"]
              if e.get("ph") == "M"}
    assert "trainer-0" in tracks


def test_trace_event_cap_counts_drops(tmp_path):
    reg = MetricsRegistry(enabled=True, trace=True, max_events=3)
    for _ in range(10):
        with reg.span("engine/step"):
            pass
    assert len(reg.trace_json()["traceEvents"]) == 4  # 3 spans + metadata
    assert reg.counters["telemetry/trace_events_dropped"] == 7


# ---------------------------------------------------------------------------
# schema validators (the CI smoke leg's teeth)
# ---------------------------------------------------------------------------
def _write_jsonl(path, recs):
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))


def _rec(step, counters, gauges=None):
    return {"ts": 0.0, "uptime_s": float(step), "counters": counters,
            "gauges": gauges or {}, "hists": {}, "step": step}


def test_validator_accepts_known_and_rejects_unknown_names(tmp_path):
    p = tmp_path / "m.jsonl"
    _write_jsonl(p, [_rec(1, {"engine/steps": 1.0}, {"bench/anything": 2.0})])
    assert validate_metrics_jsonl(str(p)) == 1

    _write_jsonl(p, [_rec(1, {"engine/steps": 1.0, "engine/stepz": 1.0})])
    try:
        validate_metrics_jsonl(str(p))
    except ValueError as e:
        assert "engine/stepz" in str(e)
    else:
        raise AssertionError("unknown metric name must fail validation")


def test_validator_rejects_decreasing_counters_and_missing_required(tmp_path):
    p = tmp_path / "m.jsonl"
    _write_jsonl(p, [_rec(1, {"engine/steps": 5.0}),
                     _rec(2, {"engine/steps": 3.0})])
    try:
        validate_metrics_jsonl(str(p))
    except ValueError as e:
        assert "decreased" in str(e)
    else:
        raise AssertionError("non-monotone counter must fail validation")

    _write_jsonl(p, [_rec(1, {"pipeline/produced": 1.0})])
    try:
        validate_metrics_jsonl(str(p))
    except ValueError as e:
        assert "engine/steps" in str(e)
    else:
        raise AssertionError("missing required counter must fail validation")


def test_known_metrics_cover_instrumentation_sites():
    # grep-level safety net: names used by the instrumented modules must be
    # documented (KNOWN_METRICS is the schema CI validates against)
    for name in ("pipeline/produced", "pipeline/producer_wait_s",
                 "pipeline/consumer_wait_s", "pipeline/queue_depth",
                 "runtime/steps", "runtime/stale_steps", "runtime/staleness",
                 "store/flush_calls", "store/pend_dropped",
                 "kvstore/pull_bytes", "kvstore/push_bytes",
                 "optim/dispatch_fused", "optim/dispatch_jnp",
                 "engine/steps", "step/loss", "step/pend_dropped"):
        assert name in telemetry.KNOWN_METRICS, name


# ---------------------------------------------------------------------------
# TelemetryHook through the engine loop
# ---------------------------------------------------------------------------
def _fake_step(state, batch):
    return state + 1, {"loss": 0.5, "pos_score": 1.0, "neg_score": -1.0}


def test_telemetry_hook_writes_valid_jsonl_and_trace(tmp_path):
    mpath, tpath = tmp_path / "m.jsonl", tmp_path / "t.json"
    with telemetry.active(trace=True) as reg:
        # statics discovered "at trace time" before the first step completes
        telemetry.trace_inc("kvstore/pull_rows", 64)
        telemetry.trace_inc("kvstore/pull_bytes", 1024)
        hook = TelemetryHook(metrics_out=str(mpath), trace_out=str(tpath),
                             every=4)
        train_loop(_fake_step, 0, lambda: (None, {"queue_depth": 3}),
                   n_steps=10, hooks=[hook], prefetch=False)
        assert reg.counters["engine/steps"] == 10
        # statics replayed every step: counter = per-step * steps
        assert reg.counters["kvstore/pull_rows"] == 64 * 10
        assert reg.gauges["kvstore/pull_rows_per_step"] == 64
        assert reg.counters["kvstore/pull_bytes"] == 1024 * 10
    n = validate_metrics_jsonl(str(mpath))
    assert n >= 3  # steps 4, 8, final 10
    recs = [json.loads(line) for line in mpath.read_text().splitlines()]
    assert [r["step"] for r in recs] == [4, 8, 10]
    steps = [r["counters"]["engine/steps"] for r in recs]
    assert steps == sorted(steps) == [4.0, 8.0, 10.0]
    assert recs[0]["gauges"]["step/loss"] == 0.5
    assert validate_trace(str(tpath)) > 0


def test_telemetry_hook_inert_when_disabled(tmp_path):
    mpath = tmp_path / "m.jsonl"
    hook = TelemetryHook(metrics_out=str(mpath), every=2)
    train_loop(_fake_step, 0, lambda: (None, None), n_steps=6,
               hooks=[hook], prefetch=False)
    assert not mpath.exists()  # no registry enabled -> no file, no error


def _slow_batch():
    # slower than the trainers' no-op steps, so they wait for batches
    time.sleep(0.002)
    return (), None


def test_hogwild_per_trainer_tracks_and_exact_step_counts(tmp_path):
    def grad_fn(state, batch):
        return 0, {"loss": 0.0}

    def apply_fn(state, batch, grads):
        return state + 1

    n_steps, n_trainers = 30, 3
    tpath = tmp_path / "t.json"
    with telemetry.active(trace=True) as reg:
        hook = TelemetryHook(trace_out=str(tpath), every=10)
        state = train_loop(
            None, 0, None, n_steps, hooks=[hook],
            n_trainers=n_trainers, n_samplers=2,
            sampler_factory=lambda wid: _slow_batch,
            split_step=(grad_fn, apply_fn))
        assert state == n_steps  # every step's apply landed exactly once
        assert reg.counters["runtime/steps"] == n_steps
        assert reg.counters["engine/steps"] == n_steps
    validate_trace(str(tpath))
    doc = json.loads(tpath.read_text())
    tracks = {e["args"]["name"] for e in doc["traceEvents"]
              if e.get("ph") == "M"}
    for tid in range(n_trainers):
        assert f"trainer-{tid}" in tracks, tracks
    # every trainer's grad/apply phases and its waits for a batch appear as
    # spans on some track
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"runtime/grad", "runtime/apply", "pipeline/consumer_wait"} <= names


# ---------------------------------------------------------------------------
# satellite fixes: MetricsHook nan, pend-overflow surfacing
# ---------------------------------------------------------------------------
def test_metrics_hook_records_nan_for_missing_keys():
    import math

    hook = MetricsHook(keys=("loss", "pend_dropped"))
    hook.on_step(1, None, {"loss": 1.0}, None)  # no pend_dropped
    hook.on_step(2, None, {"loss": 2.0, "pend_dropped": 3.0}, None)
    hook.on_step(3, None, None, None)  # apply-phase step: no metrics at all
    assert hook.history["loss"][:2] == [1.0, 2.0]
    assert math.isnan(hook.history["loss"][2])
    assert math.isnan(hook.history["pend_dropped"][0])
    assert hook.history["pend_dropped"][1] == 3.0
    assert len(hook.history["loss"]) == len(hook.history["pend_dropped"]) == 3


def test_dense_store_counts_pend_overflow_drops():
    table = jnp.zeros((16, 4), jnp.float32)
    store = DenseStore.create(table, lr=0.1, defer=True, pend_slots=2)
    ids = jnp.arange(5, dtype=jnp.int32)  # 5 uniques into 2 slots
    grads = jnp.ones((5, 4), jnp.float32)
    store = store.apply_sparse_grads(ids, grads)
    assert int(store.pend_dropped) == 3
    store = store.flush()
    assert int(store.pend_dropped) == 3  # lifetime count survives the flush
    # within capacity: no drops accumulate
    store2 = DenseStore.create(table, lr=0.1, defer=True, pend_slots=8)
    store2 = store2.apply_sparse_grads(ids, grads)
    assert int(store2.pend_dropped) == 0


def test_logging_hook_warns_once_on_pend_drops():
    lines = []
    hook = LoggingHook(log_every=1, print_fn=lines.append)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        hook.on_step(1, None, {"loss": 0.1, "pend_dropped": 0.0}, None)
        hook.on_step(2, None, {"loss": 0.1, "pend_dropped": 7.0}, None)
        hook.on_step(3, None, {"loss": 0.1, "pend_dropped": 9.0}, None)
    pend_warns = [w for w in caught if "pend buffer overflowed" in str(w.message)]
    assert len(pend_warns) == 1  # warn-once
    assert issubclass(pend_warns[0].category, RuntimeWarning)
    assert "pend_drop" not in lines[0]
    assert "pend_drop 7" in lines[1] and "pend_drop 9" in lines[2]


def test_logging_hook_rate_counts_the_loss_sync():
    class SlowLoss:  # a device scalar whose read waits for the step
        def __float__(self):
            time.sleep(0.05)
            return 0.5

    lines = []
    hook = LoggingHook(log_every=1, batch_size=1, print_fn=lines.append)
    hook.on_step(1, None, {"loss": SlowLoss()}, None)
    rate = float(lines[0].split("(")[1].split()[0])
    assert rate <= 1 / 0.05  # the clock is read after the sync, not before


# ---------------------------------------------------------------------------
# the train path's host spans and device scopes
# ---------------------------------------------------------------------------
def test_telemetry_imports_only_the_standard_library():
    src = open(telemetry.__file__).read()
    mods = set()
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Import):
            mods |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            mods.add(node.module.split(".")[0])
    assert mods - {"__future__"} <= set(sys.stdlib_module_names), mods


def _tiny_kge(**kw):
    from repro.common.config import KGEConfig

    base = dict(name="t", model="transe_l2", n_entities=64, n_relations=6,
                dim=16, batch_size=16, neg_sample_size=8, neg_group_size=8,
                loss="self_adv")
    base.update(kw)
    return KGEConfig(**base)


def test_batch_to_device_counts_one_transfer():
    """One ``batch_to_device`` call is one host-to-device transfer of
    ``4 * (3b + 2 * ng * k)`` bytes."""
    import numpy as np

    from repro.core.kge_model import batch_to_device
    from repro.core.sampling import JointSampler

    cfg = _tiny_kge()
    rng = np.random.default_rng(0)
    trip = np.stack([rng.integers(0, cfg.n_entities, 200),
                     rng.integers(0, cfg.n_relations, 200),
                     rng.integers(0, cfg.n_entities, 200)], 1)
    batch = JointSampler(trip, cfg.n_entities, cfg, rng).sample()
    b, ng, k = cfg.batch_size, cfg.n_neg_groups, cfg.neg_sample_size
    with telemetry.active() as reg:
        batch_to_device(batch)
        assert reg.counters["pipeline/to_device_transfers"] == 1
        assert reg.counters["pipeline/to_device_bytes"] == 4 * (3 * b + 2 * ng * k)
        batch_to_device(batch)
        assert reg.counters["pipeline/to_device_transfers"] == 2


def _train_path(cfg, n_steps, step_fn=None, delay=0.0):
    """``train_loop`` fed the way the trainer feeds it: the sampler and the
    host-to-device copy in the producer thread."""
    import numpy as np

    from repro.core.kge_model import batch_to_device, init_state, make_train_step
    from repro.core.sampling import JointSampler

    rng = np.random.default_rng(0)
    trip = np.stack([rng.integers(0, cfg.n_entities, 200),
                     rng.integers(0, cfg.n_relations, 200),
                     rng.integers(0, cfg.n_entities, 200)], 1)
    sampler = JointSampler(trip, cfg.n_entities, cfg, rng)

    def feed():
        time.sleep(delay)  # a sampler slower than the step: the loop waits
        return batch_to_device(sampler.sample()), None

    seen = []

    class Seen(Hook):
        def on_step(self, i, state, metrics, stats):
            seen.append(i)

    state = init_state(cfg, jax.random.key(0))
    train_loop(step_fn or make_train_step(cfg), state, feed, n_steps, hooks=[Seen()])
    return seen


def test_train_loop_records_host_spans_on_their_threads():
    cfg = _tiny_kge()
    with telemetry.active(trace=True) as reg:
        _train_path(cfg, 6, step_fn=lambda st, b: (st, {"loss": 0.0}), delay=0.01)
        doc = reg.trace_json()
    tracks = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
              if e.get("ph") == "M"}
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    where = {}
    for e in spans:
        where.setdefault(e["name"], set()).add(tracks[e["tid"]])
    main = threading.main_thread().name
    assert where["pipeline/sample"] == {"sampler-0"}
    assert where["sampler/sample"] == where["pipeline/to_device"] == {"sampler-0"}
    assert where["pipeline/consumer_wait"] == where["engine/hooks"] == {main}
    assert where["engine/step"] == {main}
    outer = [e for e in spans if e["name"] == "pipeline/sample"]
    for name in ("sampler/sample", "pipeline/to_device"):
        inner = [e for e in spans if e["name"] == name]
        assert len(inner) >= 6
        for e in inner:  # each nests in one pipeline/sample span
            assert any(o["ts"] <= e["ts"] and e["ts"] + e["dur"] <= o["ts"] + o["dur"]
                       for o in outer), (name, e)
    hooks = [e for e in spans if e["name"] == "engine/hooks"]
    assert len(hooks) == 6


def test_train_path_records_nothing_with_telemetry_off():
    reg = telemetry.get_registry()
    assert not reg.enabled and reg._annotation is None
    seen = _train_path(_tiny_kge(), 3, step_fn=lambda st, b: (st, {"loss": 0.0}))
    assert seen == [1, 2, 3]
    assert reg.trace_json()["traceEvents"] == []
    assert reg.counters == {} and reg.gauges == {}


def test_span_is_a_profiler_annotation_while_tracing(tmp_path):
    from jax.profiler import ProfileData

    reg = MetricsRegistry(enabled=True, trace=True)
    assert reg._annotation is jax.profiler.TraceAnnotation
    assert MetricsRegistry(enabled=True)._annotation is None
    jax.profiler.start_trace(str(tmp_path))
    with reg.span("sampler/sample"):
        time.sleep(0.01)
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    found = [e for p in ProfileData.from_file(str(path)).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events if e.name == "sampler/sample"]
    assert len(found) == 1 and found[0].duration_ns >= 1e7
    assert [e["name"] for e in reg.trace_json()["traceEvents"]
            if e.get("ph") == "X"] == ["sampler/sample"]


@pytest.mark.parametrize("fused", [False, True], ids=["jnp", "fused"])
def test_train_step_ops_carry_the_phase_scopes(fused):
    import numpy as np

    from repro.core.kge_model import batch_to_device, init_state, make_train_step
    from repro.core.sampling import JointSampler
    from repro.optim.sparse_adagrad import set_use_kernel

    cfg = _tiny_kge()
    set_use_kernel(fused)
    try:
        step = make_train_step(cfg)
        state = init_state(cfg, jax.random.key(0), overlap=True)
        trip = np.zeros((4, 3), np.int64)
        batch = batch_to_device(JointSampler(trip, cfg.n_entities, cfg,
                                             np.random.default_rng(0)).sample())
        text = step.lower(state, batch).as_text(debug_info=True)
    finally:
        set_use_kernel(None)
    for scope in ("kge.score_grad", "kge.adagrad_dedup", "kge.adagrad_update",
                  "kge.flush", "kge.apply"):
        assert f"/{scope}/" in text, scope
    assert "jit(train_step)/" in text and "jit(<unknown>)" not in text
    # the T5 deferred apply is a sparse update inside the flush
    assert "kge.flush/kge.adagrad_dedup/" in text
    assert "kge.flush/kge.adagrad_update/" in text
