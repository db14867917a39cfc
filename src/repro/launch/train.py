"""KGE training driver (the paper's workload).

Single-machine (many-core) mode:
    PYTHONPATH=src python -m repro.launch.train --dataset fb15k --model transe_l2 \
        --steps 2000 --scale 0.2 --eval

Hogwild multi-trainer / multi-sampler (paper §3.1/§3.3, launch/runtime.py):
    PYTHONPATH=src python -m repro.launch.train --dataset fb15k \
        --trainers 4 --samplers 4 --steps 2000

Distributed mode (one KVStore machine per device by default — ``--mesh
4x1`` on a four-chip v5e host; ``machines x servers`` in general):
    PYTHONPATH=src python -m repro.launch.train --dataset fb15k --distributed \
        --mesh 4x1 --steps 500 --partitioner metis

All of the paper's techniques are switchable:
    --neg-mode joint|naive        (T1)
    --neg-deg-ratio 0.5           (T2)
    --partitioner metis|random    (T3)
    --no-overlap                  (T5 off — applies to BOTH modes now that
                                   the single-machine path supports overlap)
    --use-kernel                  (Pallas kge_score)
    --trainers N                  (§3.1 Hogwild trainers per machine; in the
                                   single-machine joint path each trainer
                                   computes gradients against a possibly
                                   stale shared store and applies them to the
                                   latest one; in naive/distributed modes
                                   trainers share the whole-step StoreSlot
                                   swap — overlapping sampling and hook work)
    --samplers N                  (§3.3 sampler workers feeding one bounded
                                   batch queue, each with its own RNG stream)
    --eval-every K                (periodic filtered MRR during training,
                                   single-machine mode; also enables the
                                   final eval)

Multi-trainer disables T5 overlap (Hogwild already overlaps updates with
compute; the deferred buffers are single-writer by design — see the contract
in embeddings/store.py).

Both modes run through launch/engine.train_loop — the mode only decides the
step function, the sampler, and the store backend (see core/step.py).
``main(argv, hooks)`` returns the final training state, and ``hooks`` are
appended to the loop's own (chip_smoke.py drives the trainer this way).
"""

from __future__ import annotations

import argparse
import dataclasses


def main(argv=None, hooks=()):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="fb15k", choices=["fb15k", "wn18", "freebase"])
    ap.add_argument("--model", default="transe_l2")
    ap.add_argument("--dim", type=int, default=0)
    ap.add_argument("--steps", type=int, default=1000)
    ap.add_argument("--batch-size", type=int, default=0)
    ap.add_argument("--neg", type=int, default=0)
    ap.add_argument("--neg-mode", default="joint", choices=["joint", "naive"])
    ap.add_argument("--neg-deg-ratio", type=float, default=-1.0)
    ap.add_argument("--lr", type=float, default=0.0)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="synthetic graph scale vs the paper's dataset")
    ap.add_argument("--eval", action="store_true")
    ap.add_argument("--eval-n", type=int, default=2000)
    ap.add_argument("--eval-every", type=int, default=0,
                    help="periodic eval every K steps (single-machine mode)")
    ap.add_argument("--trainers", type=int, default=1,
                    help="Hogwild trainer threads per machine (paper §3.1)")
    ap.add_argument("--samplers", type=int, default=1,
                    help="sampler worker threads (paper §3.3)")
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--pipeline-depth", type=int, default=0, choices=[0, 1],
                    help="distributed only: 1 = double-buffered KVStore pull "
                         "prefetch (issue the pull for batch t+1 before the "
                         "push of batch t; one-step-stale reads)")
    ap.add_argument("--push-every", type=int, default=1,
                    help="distributed only: coalesce remote grad pushes in "
                         "per-peer merge buffers and flush them as one "
                         "deduplicated all_to_all every K steps")
    ap.add_argument("--mesh", default="",
                    help="machines x servers, e.g. 4x1; default: one "
                         "machine per device")
    ap.add_argument("--partitioner", default="metis", choices=["metis", "random"])
    ap.add_argument("--no-overlap", action="store_true")
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--remote-capacity", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=100)
    ap.add_argument("--metrics-out", default="",
                    help="append JSONL telemetry snapshots here "
                         "(schema: docs/TELEMETRY.md)")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome trace-event JSON here "
                         "(load in Perfetto; one track per trainer/sampler)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--save-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.common.compile_cache import use_compile_cache

    use_compile_cache()
    dev = jax.devices()
    print(f"platform: {dev[0].platform} ({dev[0].device_kind}) "
          f"x{len(dev)}")

    if args.metrics_out or args.trace_out:
        from repro.common import telemetry

        telemetry.enable(trace=bool(args.trace_out))

    from repro.configs import KGE_DATASETS
    from repro.data.kg_synth import fb15k_like, freebase_like, wn18_like

    cfg = KGE_DATASETS[args.dataset]
    gen = {"fb15k": fb15k_like, "wn18": wn18_like, "freebase": freebase_like}[
        args.dataset]
    kg = gen(scale=args.scale if args.dataset != "freebase" else 0.001 * args.scale,
             seed=args.seed)
    upd = dict(
        model=args.model,
        n_entities=kg.n_entities,
        n_relations=kg.n_relations,
    )
    if args.dim:
        upd["dim"] = args.dim
        # the dataset config already materialized rel_dim from its own dim;
        # 0 re-derives it from the overridden dim (transr overrides below)
        upd["rel_dim"] = 0
    if args.batch_size:
        upd["batch_size"] = args.batch_size
    if args.neg:
        upd["neg_sample_size"] = args.neg
    if args.lr:
        upd["lr"] = args.lr
    if args.neg_deg_ratio >= 0:
        upd["neg_deg_ratio"] = args.neg_deg_ratio
    if args.no_overlap:
        upd["overlap_update"] = False
    if args.remote_capacity:
        upd["remote_capacity"] = args.remote_capacity
    if args.model == "transr":
        upd["rel_dim"] = min(64, cfg.dim)
    upd["partitioner"] = args.partitioner
    cfg = dataclasses.replace(cfg, **upd)
    print(f"graph: {kg.n_entities} entities, {kg.n_relations} relations, "
          f"{kg.triplets.shape[0]} triplets")

    pairwise_fn = None
    if args.use_kernel:
        from repro.kernels.kge_score.ops import kernel_pairwise_fn

        pairwise_fn = kernel_pairwise_fn

    if not args.distributed and (args.pipeline_depth or args.push_every > 1):
        ap.error("--pipeline-depth/--push-every require --distributed "
                 "(they pipeline the KVStore collectives)")

    if args.distributed:
        return _train_distributed(args, cfg, kg, pairwise_fn, hooks)
    return _train_single(args, cfg, kg, pairwise_fn, hooks)


def _train_single(args, cfg, kg, pairwise_fn, extra_hooks):
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.common.checkpoint import latest_step, restore_checkpoint
    from repro.core import eval as E
    from repro.core.kge_model import (
        batch_to_device, flush_state, init_state, make_hogwild_step,
        make_train_step, naive_train_step,
    )
    from repro.core.sampling import JointSampler, NaiveSampler
    from repro.data.pipeline import worker_rngs
    from repro.launch.engine import (
        CheckpointHook, EvalHook, LoggingHook, TelemetryHook, train_loop,
    )

    rng = np.random.default_rng(args.seed)
    hogwild = args.trainers > 1
    # T5 overlap on the single-machine path (joint mode only: the naive
    # strawman keeps immediate updates, matching the paper's baseline).
    # Hogwild replaces it — see the store.py contract.
    overlap = cfg.overlap_update and args.neg_mode == "joint" and not hogwild
    if hogwild and cfg.overlap_update and args.neg_mode == "joint":
        print(f"{args.trainers} trainers: T5 overlap off "
              "(Hogwild already overlaps updates with compute)")
    state = init_state(cfg, jax.random.key(args.seed), overlap=overlap)
    split_step = None
    if args.neg_mode == "joint":
        def make_sampler(r):
            return JointSampler(kg.train, cfg.n_entities, cfg, r)

        step = make_train_step(cfg, pairwise_fn)
        if hogwild:  # stale-gradient two-phase step (paper §3.1)
            split_step = make_hogwild_step(cfg, pairwise_fn)
        to_dev = batch_to_device
    else:
        def make_sampler(r):
            return NaiveSampler(kg.train, cfg.n_entities, cfg, r)

        step = jax.jit(functools.partial(naive_train_step, cfg))
        to_dev = lambda b: {
            "h": jnp.asarray(b.h, jnp.int32), "r": jnp.asarray(b.r, jnp.int32),
            "t": jnp.asarray(b.t, jnp.int32), "neg": jnp.asarray(b.neg, jnp.int32)}
    sampler = make_sampler(rng)
    samplers = [make_sampler(r)
                for r in worker_rngs(args.seed, max(1, args.samplers))]

    def sampler_factory(wid):
        s = samplers[wid]
        return lambda: (to_dev(s.sample()), None)

    start = 0
    if args.resume and args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        state = restore_checkpoint(args.ckpt_dir, abstract)
        start = int(state.step)
        print(f"resumed from step {start}")

    flush = functools.partial(flush_state, cfg)
    hooks = [LoggingHook(args.log_every, batch_size=cfg.batch_size, start=start)]
    if args.metrics_out or args.trace_out:
        hooks.append(TelemetryHook(metrics_out=args.metrics_out or None,
                                   trace_out=args.trace_out or None,
                                   every=max(1, args.log_every)))
    if args.ckpt_dir:
        hooks.append(CheckpointHook(args.ckpt_dir, args.save_every,
                                    flush_fn=flush))

    def evaluate(state):
        state = flush(state)
        test = kg.test[: args.eval_n]
        if cfg.n_entities <= 60_000:
            fm = E.build_filter_map(kg.triplets)
            ranks = E.ranks_against_all(cfg, state, test, filter_map=fm)
        else:
            ranks = E.ranks_protocol2(cfg, state, test, kg.degrees().astype(np.float64))
        print("eval:", E.metrics_from_ranks(ranks))

    if args.eval or args.eval_every:
        hooks.append(EvalHook(evaluate, eval_every=args.eval_every))
    hooks.extend(extra_hooks)

    return train_loop(step, state, lambda: (to_dev(sampler.sample()), None),
                      args.steps, start=start, hooks=hooks,
                      n_trainers=args.trainers, n_samplers=args.samplers,
                      sampler_factory=sampler_factory, split_step=split_step)


def _train_distributed(args, cfg, kg, pairwise_fn, extra_hooks):
    import numpy as np

    import jax
    import jax.numpy as jnp

    from repro.common.checkpoint import latest_step, restore_checkpoint
    from repro.common.compat import set_mesh
    from repro.core.distributed import (
        build_dist_train_step, build_pipelined_dist_step, init_dist_state,
        make_program,
    )
    from repro.core.graph_part import cut_fraction, partition
    from repro.core.rel_part import relation_partition
    from repro.core.sampling import DistSampler
    from repro.data.pipeline import worker_rngs
    from repro.launch.engine import (
        CheckpointHook, LoggingHook, TelemetryHook, train_loop,
    )
    from repro.launch.mesh import make_mesh

    mesh_arg = args.mesh or f"{jax.device_count()}x1"
    dshape = tuple(int(x) for x in mesh_arg.split("x"))
    names = ("data", "model") if len(dshape) == 2 else ("pod", "data", "model")
    mesh = make_mesh(dshape, names)
    n_parts = int(np.prod(dshape[:-1]))
    cfg = dataclasses.replace(cfg, n_parts=n_parts)

    book = partition(kg.train, cfg.n_entities, n_parts,
                     method=args.partitioner, seed=args.seed)
    print(f"partitioner={args.partitioner} cut={cut_fraction(kg.train, book.part_of):.3f}")
    rp = relation_partition(kg.rel_counts(), n_parts, seed=args.seed)
    pipelined = args.pipeline_depth > 0 or args.push_every > 1
    if pipelined and cfg.overlap_update:
        print("pipelined KVStore I/O: T5 overlap off (the pipeline is its "
              "own single-writer one-step-stale overlap mechanism)")
        cfg = dataclasses.replace(cfg, overlap_update=False)
    if pipelined and (args.trainers > 1 or args.samplers > 1):
        raise SystemExit("--pipeline-depth/--push-every are incompatible "
                         "with --trainers/--samplers > 1 (the lookahead is "
                         "single-consumer; see launch/engine.train_loop)")
    prog = make_program(cfg, book.rows_per_part, rp.slots_per_part, rp.n_shared,
                        pipeline_depth=args.pipeline_depth,
                        push_every=args.push_every)
    sampler = DistSampler(kg.train, book, rp, cfg, np.random.default_rng(args.seed))
    if pipelined:
        step, state_sh, batch_sh = build_pipelined_dist_step(prog, mesh, pairwise_fn)
    else:
        step, state_sh, batch_sh = build_dist_train_step(prog, mesh, pairwise_fn)

    with set_mesh(mesh):
        start = 0
        if args.resume and args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
            abstract = jax.tree.map(
                lambda sd: jax.ShapeDtypeStruct(sd.shape, sd.dtype),
                prog.state_shapes())
            state = jax.device_put(restore_checkpoint(args.ckpt_dir, abstract),
                                   state_sh)
            start = int(state["step"])
            print(f"resumed from step {start}")
        else:
            state = jax.device_put(
                init_dist_state(prog, jax.random.key(args.seed)), state_sh)

        def batch_fn(s):
            def make():
                db = s.sample()
                batch = {k: jax.device_put(jnp.asarray(getattr(db, k)),
                                           batch_sh[k]) for k in batch_sh}
                return batch, db.stats
            return make

        # per-worker DistSamplers with independent RNG streams (§3.3);
        # multi-trainer here uses the whole-step StoreSlot swap (the
        # shard_map step is one fused collective program — trainers overlap
        # sampling, device_put, and hook work, not the collectives)
        samplers = ([sampler] if args.samplers <= 1 else
                    [DistSampler(kg.train, book, rp, cfg, r)
                     for r in worker_rngs(args.seed, args.samplers)])

        hooks = [LoggingHook(args.log_every,
                             batch_size=cfg.batch_size * n_parts, start=start)]
        if args.metrics_out or args.trace_out:
            hooks.append(TelemetryHook(metrics_out=args.metrics_out or None,
                                       trace_out=args.trace_out or None,
                                       every=max(1, args.log_every)))
        if args.ckpt_dir:
            hooks.append(CheckpointHook(args.ckpt_dir, args.save_every))
        hooks.extend(extra_hooks)
        state = train_loop(step, state, batch_fn(sampler), args.steps,
                           start=start, hooks=hooks, n_trainers=args.trainers,
                           n_samplers=args.samplers,
                           sampler_factory=lambda wid: batch_fn(samplers[wid]))
    print("done")
    return state


if __name__ == "__main__":
    main()
