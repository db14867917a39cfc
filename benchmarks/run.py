import os

# Benchmarks that exercise the distributed path need a small CPU mesh
# (8 devices — deliberately NOT the 512-device dry-run setting).
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""Benchmark harness — one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig3,...] [--full]

Prints ``name,us_per_call,derived`` CSV per the repo convention, and writes
``BENCH_RESULTS.json`` at the repo root — a telemetry snapshot (same schema
as ``--metrics-out`` lines, docs/TELEMETRY.md) holding every emitted row as
a ``bench/<name>`` gauge. Set BENCH_FAST=0 (or --full) for paper-scale
accuracy runs.

Mapping (see DESIGN.md §6):
    fig3    bench_negative_sampling   joint vs naive sampling (T1)
    table4  bench_degree_negatives    degree-based negatives (T2)
    fig4    bench_overlap             overlap update + relation partitioning
    fig5    bench_scaling             many-unit scaling
    fig7    bench_partitioning        METIS vs random (T3) + Table 7
    table5  bench_accuracy            per-model accuracy tables
    kernel  bench_kernels             T1 GEMM arithmetic intensity
    sparse_adagrad bench_kernels      fused Adagrad kernel HBM traffic
    roofline bench_roofline           dry-run roofline table (pod scale)
    hogwild bench_hogwild             §3.1 multi-trainer triplets/s scaling
    pipeline bench_pipeline           pipelined pull prefetch + coalesced push
"""

import argparse
import json
import pathlib
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    if args.full:
        os.environ["BENCH_FAST"] = "0"

    from repro.common import telemetry
    from repro.common.compile_cache import use_compile_cache

    use_compile_cache()
    telemetry.enable()

    from benchmarks import (
        bench_accuracy, bench_capacity, bench_degree_negatives, bench_hogwild,
        bench_kernels, bench_negative_sampling, bench_overlap,
        bench_partitioning, bench_pipeline, bench_roofline, bench_scaling,
    )

    suites = {
        "fig3": bench_negative_sampling.run,
        "table4": bench_degree_negatives.run,
        "fig4": bench_overlap.run,
        "fig5": bench_scaling.run,
        "fig7": bench_partitioning.run,
        "capacity": bench_capacity.run,
        "table5": bench_accuracy.run,
        "kernel": bench_kernels.run,
        "sparse_adagrad": bench_kernels.run_sparse_adagrad,
        "roofline": bench_roofline.run,
        "hogwild": bench_hogwild.run,
        "pipeline": bench_pipeline.run,
    }
    wanted = [w for w in args.only.split(",") if w] or list(suites)
    print("name,us_per_call,derived")
    failed = []
    for name in wanted:
        t0 = time.time()
        try:
            suites[name]()
        except Exception:  # run the other suites, then exit non-zero
            traceback.print_exc()
            failed.append(name)
            print(f"{name}/ERROR,0.0,see stderr", flush=True)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)

    out = pathlib.Path(__file__).resolve().parent.parent / "BENCH_RESULTS.json"
    out.write_text(json.dumps(
        telemetry.snapshot(suites=wanted), indent=2) + "\n")
    print(f"# wrote {out}", file=sys.stderr)
    if failed:
        sys.exit(f"suites failed: {','.join(failed)}")


if __name__ == "__main__":
    main()
