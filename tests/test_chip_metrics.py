"""The chip benchmark's readers of the train path's spans and device scopes,
on built window traces: each reads its span or scope, and reads nothing
where the window holds none (as in a program that lacks them)."""

import importlib.util
import pathlib
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import trace_reduce  # noqa: E402

STEPS = 4


def _reader(name):
    spec = importlib.util.spec_from_file_location(name, CHIP / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _window():
    # a v5e trace names an op by its HLO text; the scope is in the op name
    # the compiler keeps (the stats) or, for a kernel, in its own name
    dedup = ('%kge.adagrad_dedup.2 = (f32[4096,512]) custom-call(%copy.131), '
             'custom_call_target="tpu_custom_call"')
    ops = {"/device:TPU:0": [
        ("fusion.3", 0, 10), ("fusion.7", 10, 14), (dedup, 20, 60),
        ("fusion.18", 60, 66), ("copy.179", 70, 72), (dedup, 80, 100)]}
    meta = {"fusion.3": "jit(train_step)/kge.score_grad/transpose(jvp())/dot_general",
            "fusion.7": "jit(train_step)/kge.score_grad/jvp()/mul",
            dedup: "jit(train_step)/kge.flush/kge.adagrad_dedup/kge.adagrad_dedup/pallas_call",
            "fusion.18": "jit(train_step)/kge.flush/kge.adagrad_update/scatter"}
    spans = {"sampler/sample": [1e-3, 3e-3], "pipeline/to_device": [2e-3],
             "pipeline/sample": [9e-3, 9e-3]}
    return trace_reduce.WindowTrace((0, 100), ops, [], spans, {}, meta)


@pytest.mark.parametrize("name,want", [
    ("sampler_ms_per_batch.train", 2.0),
    ("to_device_ms_per_batch.train", 2.0),
    ("score_grad_ms_per_step.train", 14e-6 / STEPS),
    ("adagrad_dedup_ms_per_step.train", 60e-6 / STEPS),
    ("adagrad_update_ms_per_step.train", 6e-6 / STEPS),
])
def test_reader_reads_its_span_or_scope(name, want):
    assert _reader(name)({"trace": _window(), "steps": STEPS}) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "sampler_ms_per_batch.train", "to_device_ms_per_batch.train",
    "score_grad_ms_per_step.train", "adagrad_dedup_ms_per_step.train",
    "adagrad_update_ms_per_step.train",
])
def test_reader_reads_nothing_in_an_empty_window(name):
    # the parent program's window: its ops carry no kge.* scope, its host
    # has no sampler/sample or pipeline/to_device span
    ops = {"/device:TPU:0": [("_unknown_.4", 0, 50), ("copy.179", 50, 60)]}
    meta = {"_unknown_.4": "jit(<unknown>)/pallas_call"}
    old = trace_reduce.WindowTrace((0, 100), ops, [], {"pipeline/sample": [9e-3]}, {}, meta)
    empty = trace_reduce.WindowTrace((0, 100), {}, [], {}, {})
    for trace in (old, empty):
        assert _reader(name)({"trace": trace, "steps": STEPS}) is None
