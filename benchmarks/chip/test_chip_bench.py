"""CPU tests of the chip benchmark: its counts, its trace reduction, how it
finds cells by name, its refusal without a chip or without a per-layer
reading, and its comparison, which must pass a sound run and fail the
control and each planted fault."""

import copy
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import counts  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

ROOT = HERE.parents[1]
TRAIN_CELLS = ("fb15k-transe_l2.train", "fb15k-distmult-d2000.train")
SEED = 2**31 + 7


# ---- counts ---------------------------------------------------------------
@pytest.mark.parametrize("model,b,k,groups,d,want", [
    # transe_l2: positive 4bd = 32; per mode 4*3*2 + 3*2*2 + 4*3*(2*2+3) = 120
    ("transe_l2", 4, 3, 1, 2, 3 * (32 + 2 * 120)),
    # two groups of 2: per mode and group 2*3*2 + 3*2*2 + 2*3*7 = 66
    ("transe_l2", 4, 3, 2, 2, 3 * (32 + 2 * 2 * 66)),
    # distmult: positive 3bd = 24; per mode 4*2 + 4*3*2*2 = 56
    ("distmult", 4, 3, 1, 2, 3 * (24 + 2 * 56)),
    # the FB15k Table 3 shape: 1,638,400 + 2 * 211,935,232 forward
    ("transe_l2", 1024, 256, 1, 400, 1_276_526_592),
])
def test_train_step_flops(model, b, k, groups, d, want):
    assert counts.train_step_flops(model, b, k, groups, d) == want


def test_sparse_adagrad_bytes():
    # 10 rows of 400 float32: row and accumulator read and written, gradient read
    assert counts.sparse_adagrad_bytes(10, 400) == 5 * 10 * 400 * 4


# ---- trace reduction ------------------------------------------------------
def _built_trace():
    ops = {"/device:TPU:0": [("fusion.1", 10, 30), ("_update_kernel", 20, 40),
                             ("_dedup_kernel", 60, 70)]}
    host = [("engine/step", 0, 45), ("pipeline/sample", 40, 60), ("engine/step", 70, 100)]
    return trace_reduce.WindowTrace((0, 100), ops, host, {"pipeline/sample": [2e-8]},
                                    {"pipeline/consumer_wait_s": 0.5})


def test_trace_busy_is_union_of_device_intervals():
    t = _built_trace()
    assert t.window_s == pytest.approx(100e-9)
    assert t.busy_s == pytest.approx(40e-9)  # [10, 40] and [60, 70]


def test_trace_kernel_time_by_name():
    t = _built_trace()
    assert t.kernel_seconds(("_dedup_kernel", "_update_kernel")) == pytest.approx(30e-9)
    assert t.kernel_seconds(("no_such_kernel",)) == 0


def _load_metric_module(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_kernel_time_by_op_name_stat():
    """The roofline's kernels are found whether the trace names them by the
    kernel, by their HLO text or only in their stats."""
    # a v5e trace names each op by its HLO text
    call = ('%_unknown_.6 = (f32[1024,512]) custom-call(%copy.161, %pad.2), '
            'custom_call_target="tpu_custom_call"')
    ops = {"/device:TPU:0": [("fusion.1", 10, 30), ("_unknown_.4", 40, 55),
                             ("_unknown_.5", 60, 70), (call, 80, 84),
                             ("%copy.180 = f32[14951,400] copy(%get-tuple-element.34)", 90, 95)]}
    meta = {"fusion.1": "jit(<unknown>)/mul", "_unknown_.4": "jit(<unknown>)/pallas_call",
            "_unknown_.5": "jit(<unknown>)/pallas_call"}
    t = trace_reduce.WindowTrace((0, 100), ops, [], {}, {}, meta)
    kernels = _load_metric_module("sparse_adagrad_roofline").KERNELS
    assert t.kernel_seconds(kernels) == pytest.approx(29e-9)


def test_trace_breakdown_labels_gaps_by_host_span():
    b = _built_trace().breakdown()
    assert dict(b["device_ops"]) == pytest.approx(
        {"fusion.1": 20e-9, "_update_kernel": 20e-9, "_dedup_kernel": 10e-9})
    # gaps [0, 10] and [70, 100] under engine/step, [40, 60] under the sampler
    assert dict(b["idle_gaps"]) == pytest.approx(
        {"engine/step": 40e-9, "pipeline/sample": 20e-9})


def test_trace_reduction_of_a_recorded_profile(tmp_path):
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        f(x).block_until_ready()
        time.sleep(0.05)
    jax.profiler.stop_trace()
    # a program span that opened 10 ms into the window, on time.perf_counter
    ev = [{"name": "pipeline/sample", "ph": "X", "ts": 10e3, "dur": 5e3},
          {"name": "pipeline/sample", "ph": "X", "ts": -1e6, "dur": 5e3}]
    t = trace_reduce.reduce_profile(str(tmp_path), t0, ev, t0, {})
    assert t.window_s >= 0.05
    assert t.spans("pipeline/sample") == [pytest.approx(5e-3)]
    assert t.busy_s == 0  # the CPU has no device plane


# ---- cells found by name --------------------------------------------------
def test_new_workload_file_is_found_by_name(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    chip = tmp_path / "benchmarks" / "chip"
    (chip / "workloads" / "train-long.json").write_text(json.dumps(
        {"entry": "train", "check_steps": 3, "sync_every": 500,
         "overrides": {"batch_size": 2048}}))
    name = "fb15k-transe_l2.train-long"
    (chip / "limits" / f"{name}.json").write_text(
        (chip / "limits" / "fb15k-transe_l2.train.json").read_text())
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": "fb15k-transe_l2",
                               "traffic": "train-long", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "fb15k-transe_l2.train" in m.get("workloads", []):
            m["workloads"].append(name)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = run.load_cell(name, root=tmp_path)
    assert cell["traffic"]["sync_every"] == 500
    assert cell["traffic"]["overrides"] == {"batch_size": 2048}
    assert cell["config"]["kge"]["model"] == "transe_l2"
    assert {m["name"] for m in cell["end_to_end"]} == {"triplets_per_s", "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        m["name"] for m in run.load_cell("fb15k-transe_l2.train")["per_layer"]}


def test_every_cell_has_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell["per_layer"] and len(cell["end_to_end"]) >= 2
        for m in cell["per_layer"]:
            assert (cell["metrics_dir"] / f"{m['name']}.py").is_file()


# ---- refusal without a chip -----------------------------------------------
def test_command_refuses_a_cpu_only_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", TRAIN_CELLS[0],
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == run.NO_CHIP
    assert "no chip" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_missing_per_layer_reading_fails_the_run(monkeypatch, capsys):
    cell = run.load_cell(TRAIN_CELLS[0])
    result = {"correct": True, "attempted": 1, "failed": 0, "checks": {},
              "metrics": {cell["per_layer"][0]["name"]: {"value": 1.0, "unit": "%"}}}
    monkeypatch.setattr(run, "execute", lambda *a: (result, None))
    argv = ["--workload", cell["name"], "--seed", str(SEED), "--seconds", "1"]
    assert run.main(argv + ["--trace", "1"], require_chip=False) == run.NOTHING_READ
    assert "found nothing to read" in capsys.readouterr().err
    assert run.main(argv + ["--trace", "0"], require_chip=False) == 0


# ---- the sampler's batches, checked on their own --------------------------
def _batches(fault, n=3):
    import graph as G

    g = G.make_graph(600, 24, 9000, seed=SEED, n_clusters=4)
    kge = {"n_entities": 600, "n_relations": 24, "batch_size": 64,
           "neg_sample_size": 64, "neg_group_size": 16, "neg_deg_ratio": 0.0}
    rng = np.random.default_rng(SEED)
    out = []
    for _ in range(n):
        p = g.train[rng.integers(0, g.train.shape[0], 64)]
        b = {"h": p[:, 0], "r": p[:, 1], "t": p[:, 2],
             "neg": rng.integers(0, 600, (2, 4, 64))}
        if fault == "head_tail_swapped":
            b["h"], b["t"] = b["t"], b["h"]
        elif fault == "relation_out_of_range":
            b["r"] = b["r"] + 24
        elif fault == "negatives_half_range":
            b["neg"] = b["neg"] // 2
        elif fault == "one_group":
            b["neg"] = b["neg"][:, :1]
        out.append(b)
    return out, g.train, kge


@pytest.mark.parametrize("fault,number", [
    (None, None),
    ("head_tail_swapped", "batch_faults"),
    ("relation_out_of_range", "batch_faults"),
    ("negatives_half_range", "neg_spread"),
    ("one_group", "batch_faults"),
])
def test_batch_check(fault, number):
    import check

    limits = json.loads((HERE / "limits" / f"{TRAIN_CELLS[0]}.json").read_text())
    got = check.batch_numbers(*_batches(fault))
    failed = {k for k, v in got.items() if v > limits[k]}
    assert (number in failed) if number else not failed, got


# ---- the comparison: sound runs pass, the control and faults fail ---------
def _tiny(name):
    """The cell at a size a CPU test holds; the limits are the cell's own."""
    cell = copy.deepcopy(run.load_cell(name))
    cell["config"]["dataset"].update(n_entities=600, n_relations=24, n_triplets=9000)
    cell["config"]["kge"].update(n_entities=600, n_relations=24, dim=32, rel_dim=32,
                                 batch_size=64, neg_sample_size=64, neg_group_size=16)
    cell["traffic"].update(sync_every=10)
    return cell


def _plant(fault, monkeypatch):
    import jax.numpy as jnp

    from repro.core import kge_model, losses, sampling

    if fault == "state_unchanged":
        step = kge_model.train_step
        monkeypatch.setattr(kge_model, "train_step",
                            lambda cfg, state, batch, pairwise_fn=None:
                            (state, step(cfg, state, batch, pairwise_fn)[1]))
    elif fault == "half_batch":
        loss = losses.kge_loss

        def half(kind, pos, neg, margin=1.0):
            b = pos.shape[0] // 2  # rows per corruption side
            keep = jnp.concatenate([jnp.arange(b // 2), b + jnp.arange(b // 2)])
            return loss(kind, pos[keep], neg[keep], margin)

        monkeypatch.setattr(losses, "kge_loss", half)
    elif fault == "negatives_half_range":
        monkeypatch.setattr(sampling.JointSampler, "_uniform_negs",
                            lambda self, n: self.rng.integers(0, self.n_entities // 2, n))
    elif fault == "head_tail_swapped":
        sample = sampling.JointSampler.sample

        def swapped(self):
            b = sample(self)
            b.h, b.t = b.t, b.h
            return b

        monkeypatch.setattr(sampling.JointSampler, "sample", swapped)


@pytest.mark.parametrize("name,fault,want", [
    (TRAIN_CELLS[0], None, True),
    (TRAIN_CELLS[0], "state_unchanged", False),
    (TRAIN_CELLS[0], "half_batch", False),
    (TRAIN_CELLS[0], "negatives_half_range", False),
    (TRAIN_CELLS[0], "head_tail_swapped", False),
    (TRAIN_CELLS[1], None, True),
    (TRAIN_CELLS[1], "state_unchanged", False),
    (TRAIN_CELLS[1], "half_batch", False),
    (TRAIN_CELLS[1], "negatives_half_range", False),
])
def test_run_is_correct_only_when_sound(name, fault, want, monkeypatch):
    monkeypatch.setattr(run, "_compile_cache", lambda: None)
    _plant(fault, monkeypatch)
    result, _ = run.execute(_tiny(name), SEED, 0.3, trace=False)
    assert result["correct"] is want, result["checks"]
    assert result["attempted"] > 0
    assert set(result["checks"]) == set(_tiny(name)["limits"])


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_control_fails_the_limits(name, monkeypatch):
    """The reference one precision below the configuration's (bfloat16), put
    in the program's place, must fail the cell's limits."""
    import check
    import readings

    monkeypatch.setattr(run, "_compile_cache", lambda: None)
    cell = _tiny(name)
    _, out = run.execute(cell, SEED, 0.3, trace=False)
    control = readings.train_readings(cell, SEED, out)["control"]
    limits = {k: v for k, v in cell["limits"].items() if k in control}
    assert not check.verdict(control, limits), control
