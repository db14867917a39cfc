"""CPU tests of the TransR cell (entry ``train_proj``, ``reference_transr.py``,
``counts_proj.py`` and its readers) and of the degree-based-negative TransE
cell: the comparison must pass a sound run and fail the control and each
planted fault."""

import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import counts_proj  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
from test_chip_bench import SEED, _load_metric_module, _plant, _tiny  # noqa: E402

TRANSR = "fb15k-transr.train"
DEGNEG = "fb15k-transe_l2.train-degneg"


# ---- counts ---------------------------------------------------------------
def test_transr_counts():
    # b=4, k=3, one group, d=2, rel_dim=3: positive 4 * (4*2*3 + 4*3) = 144;
    # per mode o 4 * (12 + 3) = 60, projected 4*3 * (12 + 9) = 252, sqrt 24
    assert counts_proj.transr_projected(4, 3, 2, 3) == 252
    assert counts_proj.train_step_flops(4, 3, 1, 2, 3) == 3 * (144 + 2 * (60 + 252 + 24))
    assert counts_proj.projected_step_flops(4, 3, 1, 2, 3) == 3 * 2 * 252
    # matrices 2 * 4*2*3 = 48; per mode 2*4*3 + 2*1*3*2 + 2*4*3 = 60
    assert counts_proj.projected_step_bytes(4, 3, 1, 2, 3) == 4 * (48 + 2 * 60)


def test_transr_counts_at_fb15k():
    """128 GFLOP a step, 127 of them in the projected scoring (0.64 ms at
    v5e's bf16 peak); 0.34 GB, 0.33 of it the matrices and their gradient."""
    assert counts_proj.train_step_flops(1024, 256, 4, 200, 200) == pytest.approx(
        1.27763e11, rel=1e-4)
    assert counts_proj.projected_step_flops(1024, 256, 4, 200, 200) == pytest.approx(
        1.26773e11, rel=1e-4)
    assert counts_proj.projected_step_bytes(1024, 256, 4, 200, 200) == pytest.approx(
        3.384e8, rel=1e-3)


# ---- readers --------------------------------------------------------------
def _ctx(ops):
    kge = run.load_cell(TRANSR)["config"]["kge"]
    trace = trace_reduce.WindowTrace((0, 10**9), {"/device:TPU:0": ops}, [], {}, {})
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    return {"kge": kge, "steps": 100, "seconds": 1.0, "trace": trace, "peaks": peaks,
            "unique_rows": {"entity": 3357.0, "rel": 368.0, "proj": 368.0}}


def test_transr_readers_read_their_kernels():
    ms = 10**6  # ns
    ops = [("kge.transr_score.3", 0, 40 * ms), ("kge.transr_score_bwd.4", 40 * ms, 120 * ms),
           ("kge.adagrad_update.7", 120 * ms, 200 * ms), ("fusion.2", 200 * ms, 300 * ms)]
    ctx = _ctx(ops)
    score = _load_metric_module("transr_score_roofline").read(ctx)
    # 100 steps x max(127 GFLOP / 197 TFLOP/s, 0.33 GB / 819 GB/s) over 120 ms
    assert score == pytest.approx(100 * 100 * 1.26773e11 / 197e12 / 0.120, rel=1e-5)
    upd = _load_metric_module("transr_adagrad_roofline").read(ctx)
    step_bytes = 5 * 4 * (3357 * 200 + 368 * 200 + 368 * 40_000)
    assert upd == pytest.approx(100 * 100 * step_bytes / 819e9 / 0.080)
    mfu = _load_metric_module("transr_train_mfu").read(ctx)
    assert mfu == pytest.approx(100 * 1.27763e11 * 100 / 197e12, rel=1e-3)


def test_transr_readers_read_nothing_without_kernels():
    ctx = _ctx([("fusion.2", 0, 10**8)])
    for name in ("transr_score_roofline", "transr_adagrad_roofline"):
        assert _load_metric_module(name).read(ctx) is None


# ---- the comparison: sound runs pass, the control and faults fail ---------
@pytest.mark.parametrize("name,fault,want", [
    (TRANSR, None, True),
    (TRANSR, "state_unchanged", False),
    (TRANSR, "half_batch", False),
    (DEGNEG, None, True),
])
def test_run_is_correct_only_when_sound(name, fault, want, monkeypatch):
    monkeypatch.setattr(run, "_compile_cache", lambda: None)
    _plant(fault, monkeypatch)
    result, out = run.execute(_tiny(name), SEED, 0.3, trace=False)
    assert result["correct"] is want, result["checks"]
    assert result["attempted"] > 0
    if name == TRANSR:
        assert set(out["readings"]["reference"]["grad_norm"]) == {"entity", "rel", "proj"}


def test_transr_control_fails_the_limits(monkeypatch):
    """The TransR reference in bfloat16, put in the program's place, must
    fail the cell's limits."""
    import check
    import readings_proj

    monkeypatch.setattr(run, "_compile_cache", lambda: None)
    cell = _tiny(TRANSR)
    _, out = run.execute(cell, SEED, 0.3, trace=False)
    control = readings_proj.train_readings(cell, SEED, out)["control"]
    limits = {k: v for k, v in cell["limits"].items() if k in control}
    assert not check.verdict(control, limits), control


def test_transr_entry_stops_without_the_projected_kernel(monkeypatch):
    """A program without the kernels the cell reads stops before any work."""
    import types

    import graph as G

    monkeypatch.setitem(sys.modules, "repro.kernels.kge_score", types.ModuleType("stub"))
    sys.path.insert(0, str(run.ROOT / "src"))
    import train_proj_cell

    cell = _tiny(TRANSR)
    ds = cell["config"]["dataset"]
    g = G.make_graph(ds["n_entities"], ds["n_relations"], ds["n_triplets"], seed=SEED)
    with pytest.raises(ImportError):
        train_proj_cell.run(cell, g, SEED, 0.1, None)


def test_new_cells_are_listed_as_asked():
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[TRANSR]["chips"] == cells[DEGNEG]["chips"] == 1
    listed = {m["name"]: m["workloads"] for m in bench["per_layer"] + bench["end_to_end"]
              if "workloads" in m}
    for name in ("triplets_per_s", "sample_ms_per_batch.train",
                 "data_wait_ms_per_step.train", "device_idle_share.train",
                 "transr_train_mfu", "transr_score_roofline", "transr_adagrad_roofline"):
        assert TRANSR in listed[name], name
    for name in ("train_mfu", "sparse_adagrad_roofline"):
        assert TRANSR not in listed[name] and DEGNEG in listed[name], name
