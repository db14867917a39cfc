import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the paper's distributed KGE train step lowers,
compiles, and fits on the production mesh, and report its roofline terms.
No real allocation happens — all inputs are ShapeDtypeStructs.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --kge fb15k|wn18|freebase \
        [--kge-model transr] [--multi-pod] [--set k=v,...] [--out row.json]
"""

import argparse
import json
import sys
import time
import traceback

import jax

from repro.common import compat
from repro.common.hw import hw_for
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import format_row, roofline_from_compiled

# the production mesh the dry-run compiles for is a TPU v5e pod
TARGET_HW = hw_for("TPU v5 lite")


def dryrun_kge(dataset: str, multi_pod: bool, model: str = "",
               hlo_out: str = "", overrides: dict | None = None) -> dict:
    """Dry-run of the paper's distributed KGE train step on the target mesh."""
    import dataclasses as dc

    import numpy as np

    from repro.configs import KGE_DATASETS
    from repro.core.distributed import (
        build_dist_train_step, make_program, n_machines,
    )

    cfg = KGE_DATASETS[dataset]
    if model:
        cfg = dc.replace(cfg, model=model,
                         rel_dim=64 if model == "transr" else 0)
    if overrides:
        cfg = dc.replace(cfg, **overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    P_ = n_machines(mesh)
    cfg = dc.replace(cfg, n_parts=P_)
    servers = int(mesh.shape["model"])
    mult = 2 * servers if cfg.model in ("complex", "rotate") else servers
    if cfg.dim % mult:
        # complex-pair layout needs even dim slices per KVStore server
        cfg = dc.replace(cfg, dim=-(-cfg.dim // mult) * mult,
                         rel_dim=0 if cfg.model != "transr" else cfg.rel_dim)
    rows = -(-cfg.n_entities // P_)
    rows = ((rows + 7) // 8) * 8
    rel_slots = max(8, ((-(-cfg.n_relations // P_) + 7) // 8) * 8)
    prog = make_program(cfg, rows, rel_slots, n_shared=8)
    step, state_sh, batch_sh = build_dist_train_step(prog, mesh)

    def sds(shapes, sh_tree):
        return {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh_tree[k])
            for k, v in shapes.items()
        }

    t0 = time.time()
    with compat.set_mesh(mesh):
        lowered = step.lower(sds(prog.state_shapes(), state_sh),
                             sds(prog.batch_shapes(), batch_sh))
        compiled = lowered.compile()
    t_compile = time.time() - t0
    chips = int(np.prod(list(mesh.shape.values())))
    txt = compiled.as_text()
    if hlo_out:
        with open(hlo_out, "w") as f:
            f.write(txt)
    cost = analyze_hlo(txt, total_devices=chips)
    # MODEL_FLOPS for KGE: score flops = positives + negatives GEMMs per step
    b, k, d = cfg.batch_size, cfg.neg_sample_size, cfg.dim
    mf = P_ * (2 * 2.0 * b * k * d + 3 * 2.0 * b * d) * 3  # fwd+bwd(2x)
    rl = roofline_from_compiled(
        compiled, f"kge-{dataset}-{cfg.model}", "kge_step",
        "x".join(str(s) for s in mesh.devices.shape), chips, mf,
        hw=TARGET_HW, hlo_cost=cost)
    row = rl.row()
    row["compile_s"] = t_compile
    ma = compiled.memory_analysis()
    if ma:
        row["memory_analysis"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
        }
    print(format_row(rl), f"(compile {t_compile:.1f}s)")
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kge", required=True, help="KGE dataset dry-run")
    ap.add_argument("--kge-model", default="")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--set", default="", help="cfg overrides k=v,k=v (int/float/str)")
    ap.add_argument("--out", default="")
    ap.add_argument("--hlo-out", default="")
    args = ap.parse_args()

    try:
        overrides = {}
        for kv in [x for x in args.set.split(",") if x]:
            k, v = kv.split("=")
            for cast in (int, float, str):
                try:
                    v = cast(v)
                    break
                except ValueError:
                    continue
            overrides[k] = v
        row = dryrun_kge(args.kge, args.multi_pod, args.kge_model,
                         args.hlo_out, overrides)
    except Exception as e:
        row = {
            "arch": f"kge-{args.kge}",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
        print("FAILED:", row["error"], file=sys.stderr)
        print(row["traceback"], file=sys.stderr)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(row, f, indent=2, default=float)
    return 0 if "error" not in row else 1

if __name__ == "__main__":
    sys.exit(main())
