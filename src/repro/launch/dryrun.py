import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture × input shape × mesh) lowers,
compiles, fits, and report its roofline terms. No real allocation happens —
all inputs are ShapeDtypeStructs.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
        --shape train_4k [--multi-pod] [--out results.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun/

The KGE core has its own dry-run entry: --kge fb15k|wn18|freebase.
"""

import argparse
import json
import sys
import time
import traceback

import jax

from repro.common import compat
from repro.common.config import INPUT_SHAPES
from repro.common.hw import hw_for
from repro.launch.hlo_analysis import analyze_hlo, xla_cost_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import format_row, roofline_from_compiled

# the production mesh the dry-run compiles for is a TPU v5e pod
TARGET_HW = hw_for("TPU v5 lite")


def dryrun_arch(arch_name: str, shape_name: str, multi_pod: bool,
                use_flash: bool = False, microbatches: int = 0,
                hlo_out: str = "", overrides: dict | None = None) -> dict:
    from repro.configs import get_arch
    from repro.models.steps import (
        build_prefill_step, build_serve_step, build_train_step,
        serve_abstract_args, train_abstract_args, input_defs, abstract_inputs,
    )
    from repro.models.transformer import build_model

    cfg = get_arch(arch_name)
    import dataclasses

    if microbatches:
        cfg = dataclasses.replace(cfg, microbatches=microbatches)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = INPUT_SHAPES[shape_name]
    ok, why = cfg.supports_shape(shape)
    if not ok:
        return {"arch": arch_name, "shape": shape_name, "skipped": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(len(mesh.devices.reshape(-1)))
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    model = build_model(cfg, mesh=mesh, use_flash_prefill=use_flash)

    t0 = time.time()
    with compat.set_mesh(mesh):
        if shape.kind == "train":
            step, _ = build_train_step(model, shape=shape)
            aps, aos, batch = train_abstract_args(model, shape)
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(aps, aos, batch)
        elif shape.kind == "prefill":
            step = build_prefill_step(model, use_flash=use_flash)
            aps = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=jax.sharding.NamedSharding(mesh, s)),
                model.abstract_params(), model.param_specs(),
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
            batch = abstract_inputs(input_defs(cfg, shape, model), mesh)
            lowered = jax.jit(step).lower(aps, batch)
        else:  # decode
            step = build_serve_step(model)
            aps, caches, token, index = serve_abstract_args(model, shape)
            lowered = jax.jit(step, donate_argnums=(1,)).lower(
                aps, caches, token, index)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    txt = compiled.as_text()
    if hlo_out:
        with open(hlo_out, "w") as f:
            f.write(txt)
    cost = analyze_hlo(txt, total_devices=chips)
    rl = roofline_from_compiled(
        compiled, arch_name, shape_name, mesh_name, chips,
        model_flops=cfg.model_flops(shape), hw=TARGET_HW, hlo_cost=cost)
    row = rl.row()
    row.update(lower_s=t_lower, compile_s=t_compile)
    try:
        ma = compiled.memory_analysis()
        row["memory_analysis"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        }
    except Exception:
        pass
    ca = xla_cost_analysis(compiled)
    if ca:
        row["xla_cost_analysis"] = {
            "flops": ca.get("flops"), "bytes accessed": ca.get("bytes accessed")}
    print(format_row(rl), f"(lower {t_lower:.1f}s compile {t_compile:.1f}s)")
    return row


def dryrun_kge(dataset: str, multi_pod: bool, model: str = "",
               hlo_out: str = "", overrides: dict | None = None) -> dict:
    """Dry-run of the paper's distributed KGE train step on the target mesh."""
    import dataclasses as dc

    import numpy as np

    from repro.configs import KGE_DATASETS
    from repro.core.distributed import (
        DistKGEProgram, build_dist_train_step, machine_axis_of, make_program,
        n_machines,
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
    ap.add_argument("--arch", default="")
    ap.add_argument("--shape", default="train_4k", choices=list(INPUT_SHAPES))
    ap.add_argument("--kge", default="", help="KGE dataset dry-run")
    ap.add_argument("--kge-model", default="")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--use-flash", action="store_true")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--set", default="", help="cfg overrides k=v,k=v (int/float/str)")
    ap.add_argument("--out", default="")
    ap.add_argument("--hlo-out", default="")
    args = ap.parse_args()

    try:
        if args.kge:
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
        else:
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
            row = dryrun_arch(args.arch, args.shape, args.multi_pod,
                              args.use_flash, args.microbatches, args.hlo_out,
                              overrides=overrides)
    except Exception as e:
        row = {
            "arch": args.arch or f"kge-{args.kge}", "shape": args.shape,
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
