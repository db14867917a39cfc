"""Chip benchmark of DGL-KE training: one cell per run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in BENCHMARK.json. Its configuration
file holds the sizes, its traffic mix is ``workloads/<traffic>.json`` beside
this file, its limits are ``limits/<cell>.json`` and each per-layer metric is
read by ``metrics/<metric>.py``; nothing here names a cell. The program under
test is taken from ``src/`` of the checkout. Without a TPU, or with fewer
chips than the cell asks for, the run exits with code 3 and prints no result;
where a per-layer metric of the cell finds nothing to read in a traced run, it
exits with code 4 and prints no result.
The last line of standard output is the result as one JSON object.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
NO_CHIP = 3
NOTHING_READ = 4


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """Everything a run of cell ``name`` needs, found by name from
    BENCHMARK.json."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    here = root / HERE.relative_to(ROOT)
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return {
        "name": name,
        "chips": cell["chips"],
        "config": json.loads((root / conf["file"]).read_text()),
        "traffic": json.loads((here / "workloads" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads((here / "limits" / f"{name}.json").read_text()),
        "end_to_end": e2e,
        "per_layer": per_layer,
        "metrics_dir": here / "metrics",
    }


def _load_reader(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(f"metric_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Bench:
    """What a cell entry shares with the harness: the timed window, the
    traced window, the memory reading and the earlier output lines."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.setup_s = None
        self.memory_peak_bytes = None
        self.window = None  # the traced window's WindowTrace
        # compile requests and persistent-cache hits, in set-up and in the window
        self.compiles = {p: {"requests": 0, "cache_hits": 0} for p in ("setup", "window")}
        self._phase = "setup"

    def listen(self):
        import jax

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration" and self._phase:
                self.compiles[self._phase]["requests"] += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits" and self._phase:
                self.compiles[self._phase]["cache_hits"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def timed(self, fn):
        """Run ``fn(t0)`` as the measured window; returns its result and the
        window's seconds. With tracing on, the window is profiled."""
        import jax

        import trace_reduce

        tracer = trace_reduce.Recorder() if self.trace else None
        if tracer:
            tracer.start()
        self._phase = "window"
        t0 = time.perf_counter()
        self.setup_s = t0 - T0
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            out = fn(t0)
        t1 = time.perf_counter()
        self._phase = None
        if tracer:
            self.window = tracer.stop(t0)
            self.info(trace_reduce_s=self.window.reduce_s)
        self.info(setup_s=self.setup_s, window_s=t1 - t0, compiles={
            p: dict(c, compiled=c["requests"] - c["cache_hits"])
            for p, c in self.compiles.items()})
        return out, t1 - t0

    def read_memory(self):
        import jax

        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        self.memory_peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
        self.info(memory_peak_bytes=self.memory_peak_bytes)

    @staticmethod
    def info(**kw):
        print("info " + json.dumps(kw, default=float), flush=True)


def main(argv=None, *, require_chip: bool = True, cell: dict = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cell or load_cell(args.workload)

    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell["chips"]):
        print(f"no chip: {len(devs)} {devs[0].platform} device(s), the cell "
              f"asks for {cell['chips']} TPU chip(s)", file=sys.stderr)
        return NO_CHIP

    result, _ = execute(cell, args.seed, args.seconds, bool(args.trace))
    missing = [m["name"] for m in cell["per_layer"] if args.trace
               and m["name"] not in result["metrics"]]
    if missing:
        print(f"per-layer metrics of the cell found nothing to read: {missing}",
              file=sys.stderr)
        return NOTHING_READ
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def execute(cell: dict, seed: int, seconds: float, trace: bool):
    """One run of ``cell`` on the devices JAX has; returns the result line's
    object and what the cell's entry returned."""
    import jax

    devs = jax.devices()
    sys.path.insert(0, str(ROOT / "src"))
    _compile_cache()

    import check
    import graph as G

    bench = Bench(trace)
    bench.listen()
    ds = cell["config"]["dataset"]
    g = G.make_graph(ds["n_entities"], ds["n_relations"], ds["n_triplets"],
                     seed=seed, **ds.get("generator", {}))
    bench.info(device={"platform": devs[0].platform, "kind": devs[0].device_kind,
                       "count": len(devs)},
               graph={"entities": g.n_entities, "relations": g.n_relations,
                      "triplets": int(g.triplets.shape[0]),
                      "train": int(g.train.shape[0]), "test": int(g.test.shape[0])})

    out = __import__(f"{cell['traffic']['entry']}_cell").run(cell, g, seed, seconds, bench)

    limits = cell["limits"]
    numbers = out["numbers"]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": bench.memory_peak_bytes}
    result = {"correct": check.verdict(numbers, limits), "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        import peaks

        ctx = dict(out["layer"], cell=cell, trace=bench.window,
                   peaks=peaks.peaks_for(devs[0].device_kind))
        metrics = {}
        for m in cell["per_layer"]:
            value = _load_reader(cell["metrics_dir"] / f"{m['name']}.py")(ctx)
            if value is None:
                print(f"metric {m['name']}: nothing to read", file=sys.stderr)
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=bench.window.busy_s, window_s=bench.window.window_s)
        result.update(metrics=metrics, device=device,
                      breakdown=bench.window.breakdown())
    else:
        metrics = {m["name"]: {"value": out["metrics"][m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"] if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": bench.setup_s, "unit": "s"}
        result.update(metrics=metrics, device=device)
    result["checks"] = {k: {"value": _number(numbers[k]), "limit": limits[k]}
                        for k in limits}
    return result, out


def _compile_cache():
    """The program's persistent compilation cache (its fixed directory in the
    checkout, or JAX_COMPILATION_CACHE_DIR), keeping every program."""
    import jax

    from repro.common.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def _number(x: float):
    """A compared number as JSON can hold it: a number not finite is
    written as a string ("inf", "nan")."""
    import math

    return x if math.isfinite(x) else str(x)


if __name__ == "__main__":
    sys.exit(main())
