"""LM serving driver: prefill + batched decode for any --arch (reduced or full).

On CPU this runs the REDUCED config end-to-end (full configs are exercised by
launch/dryrun.py without allocation):

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-2.7b \
        --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--full", action="store_true", help="full (not reduced) config")
    ap.add_argument("--metrics-out", default="",
                    help="append JSONL telemetry snapshots here "
                         "(schema: docs/TELEMETRY.md)")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome trace-event JSON here (Perfetto)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.metrics_out or args.trace_out:
        from repro.common import telemetry

        telemetry.enable(trace=bool(args.trace_out))

    import jax
    import jax.numpy as jnp

    from repro.common.compile_cache import use_compile_cache
    from repro.configs import get_arch
    from repro.launch.engine import TelemetryHook, ThroughputHook, run_loop
    from repro.models.transformer import build_model

    use_compile_cache()
    cfg = get_arch(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(args.seed))
    rng = np.random.default_rng(args.seed)
    B, T = args.batch, args.prompt_len
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)

    total = T + args.gen
    caches = jax.tree.map(
        lambda d: jnp.zeros(d.shape, d.dtype),
        model.cache_defs(B, total),
        is_leaf=lambda x: hasattr(x, "materialize"))

    decode = jax.jit(model.decode_step, donate_argnums=(1,))

    # prefill + generate through the shared engine loop (prefill_step exists
    # for the batch path; the serving loop here feeds the prompt token by
    # token to fill the caches, then greedy-decodes). ThroughputHook starts
    # its clock at the first step, so the reported tok/s measures steady
    # serving throughput — jit compile time is excluded.
    out = []

    def decode_step(i, carry):
        logits, caches = carry
        if i < T:
            tok = tokens[:, i : i + 1]
        else:
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            out.append(np.asarray(tok))
        logits, caches = decode(params, caches, tok, jnp.asarray(i, jnp.int32))
        return (logits, caches), {}

    steps = T + args.gen
    hooks = [ThroughputHook(items_per_step=B, label="tok")]
    if args.metrics_out or args.trace_out:
        hooks.append(TelemetryHook(metrics_out=args.metrics_out or None,
                                   trace_out=args.trace_out or None,
                                   every=16))
    logits, _ = run_loop(
        decode_step, (None, caches), steps, hooks=hooks)
    gen = np.concatenate(out, axis=1)
    print(f"arch={cfg.name} reduced={not args.full} batch={B}")
    print(f"generated tokens:\n{gen}")
    assert np.isfinite(np.asarray(logits)).all()


if __name__ == "__main__":
    main()
