"""Smoke run of KGE training on TPU: the main path once, at full FB15k width.

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # the partitioned KVStore path on four

One chip, three phases, all through the user's entry point
(``repro.launch.train.main``) or the modules it drives:

1. ``train``: TransE-L2 on a seeded FB15k-shaped graph (14,951 entities,
   1,345 relations; d=400, b=1024, k=256) for 20 steps — ``train.main`` →
   ``engine.train_loop`` → ``core/step.py`` → ``DenseStore`` →
   ``sparse_adagrad_apply`` with the fused Pallas kernels. The loss must be
   finite and fall, and ``optim/dispatch_fused`` must count.
2. ``update``: one more step from the trained state on one batch, its
   update applied by the fused kernels and by the jnp path
   (``use_kernel=False``) to the same gradients; the tables and Adagrad
   accumulators must agree within f32 tolerance.
3. ``kge_score``: the score kernel (L2 and L1, with the L1 backward
   kernels) against the jnp reference on a small input, then a few
   ``--use-kernel`` training steps for ``transe_l2`` and ``transe_l1``.

Four chips (``--chips 4``): ``--distributed --mesh 4x1`` (four KVStore
machines, one per chip) and, to compare with, the same program at
``--mesh 1x1`` on one of those chips. The entity table must be spread over
four devices, the ``kvstore/pull_*``/``push_*`` counters must count, and
the loss must be finite and fall.

A failing check raises, so the script exits non-zero; the last line of
standard output — ``{"ok": true, "device": {...}}`` — is printed only when
every phase passed. Without a TPU the script exits non-zero before any
phase. Compile times and step times printed here are one smoke run, not a
benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
STEPS = 20
KERNEL_STEPS = 6
# f32 agreement of the fused and jnp updates on identical gradients: the two
# differ only in the summation order of duplicate rows' gradients. The step
# starts from the trained state: from a zero accumulator Adagrad's first
# step is lr * g / (|g| + eps), which turns the rounding of a near-zero
# aggregated g into an arbitrarily large difference.
UPDATE_TOL = dict(rtol=1e-5, atol=1e-6)


class CompileLog:
    """Compile seconds and persistent-cache hits, from JAX's own events."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.cache_requests = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self, since: float) -> str:
        return (f"compile {self.seconds - since:.1f} s "
                f"(persistent cache: {self.cache_hits} hits of "
                f"{self.cache_requests} requests so far)")


class Recorder:
    """Train-loop hook: per-step losses, and the clock after
    ``block_until_ready`` at the first step, step ``warm`` and the end."""

    def __init__(self, warm: int):
        self.warm = warm
        self.losses = []
        self.marks = {}

    def on_step(self, i, state, metrics, stats):
        import jax

        self.losses.append(metrics["loss"])
        if i in (1, self.warm):
            jax.block_until_ready(state)
            self.marks[i] = time.perf_counter()

    def on_end(self, i, state):
        import jax

        jax.block_until_ready(state)
        self.marks[i] = time.perf_counter()
        self.last = i

    def step_ms(self) -> float:
        span = self.marks[self.last] - self.marks[self.warm]
        return 1e3 * span / (self.last - self.warm)

    def check_loss(self, label: str):
        """Finite, and falling after the first update: Adagrad's first step
        moves every touched row by about ``lr`` per coordinate, which can
        raise the loss at step 2 (TransE-L1 does, on every path)."""
        import numpy as np

        loss = np.asarray([float(x) for x in self.losses])
        if not np.all(np.isfinite(loss)):
            raise AssertionError(f"{label}: non-finite loss {loss}")
        after = loss[1:]
        k = max(1, len(after) // 4)
        if not after[-k:].mean() < after[:k].mean():
            raise AssertionError(f"{label}: loss did not fall: {loss}")
        return (f"loss {loss[0]:.4f} (step 1), {loss[1]:.4f} (step 2) -> "
                f"{loss[-1]:.4f} (step {len(loss)})")


def _train(label, argv, steps, compile_log, extra_hooks=()):
    from repro.launch import train

    rec = Recorder(warm=min(5, steps - 1))
    c0 = compile_log.seconds
    state = train.main(argv + ["--steps", str(steps),
                               "--log-every", str(steps)],
                       hooks=[rec, *extra_hooks])
    losses = rec.check_loss(label)
    print(f"[{label}] {compile_log.line(c0)}; steady step "
          f"{rec.step_ms():.2f} ms (steps {rec.warm}-{rec.last}, "
          f"block_until_ready); {losses}")
    return state


def _counter(name: str) -> float:
    from repro.common import telemetry

    return telemetry.snapshot()["counters"].get(name, 0.0)


def _peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} bytes"


def _allclose(label, got, want, **tol):
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    if not np.allclose(got, want, **tol):
        raise AssertionError(f"{label}: max |diff| {err:.3g} exceeds {tol}")
    return err


# --------------------------------------------------------------------- phases
def phase_train(compile_log):
    from repro.optim.sparse_adagrad import use_kernel

    if not use_kernel():
        raise AssertionError("fused sparse-Adagrad kernel is off on a TPU")
    base = ["--dataset", "fb15k", "--scale", "1.0", "--model", "transe_l2"]
    state = _train("train", base, STEPS, compile_log)
    fused = _counter("optim/dispatch_fused")
    if fused <= 0:
        raise AssertionError("optim/dispatch_fused did not count")
    print(f"[train] optim/dispatch_fused={fused:g} "
          f"optim/dispatch_jnp={_counter('optim/dispatch_jnp'):g}")
    return state


def phase_update(trained, seed: int = 0):
    import jax
    import numpy as np

    from repro.configs import KGE_DATASETS
    from repro.core.kge_model import (
        apply_step, batch_to_device, flush_state, grad_step,
    )
    from repro.core.sampling import JointSampler
    from repro.data.kg_synth import fb15k_like
    from repro.optim.sparse_adagrad import set_use_kernel

    kg = fb15k_like(scale=1.0, seed=seed)
    cfg = dataclasses.replace(KGE_DATASETS["fb15k"], n_entities=kg.n_entities,
                              n_relations=kg.n_relations)
    # the trained tables with the deferred (T5) update applied, stepped with
    # immediate updates so the step's update lands in its output
    state = dataclasses.replace(flush_state(cfg, trained), pend_ids=None,
                                pend_grads=None)
    sampler = JointSampler(kg.train, cfg.n_entities, cfg,
                           np.random.default_rng(seed + 1))
    batch = batch_to_device(sampler.sample())
    grads, _ = jax.jit(functools.partial(grad_step, cfg))(state, batch)
    out = {}
    try:
        for fused in (True, False):
            set_use_kernel(fused)
            out[fused] = jax.jit(functools.partial(apply_step, cfg))(
                state, batch, grads)
    finally:
        set_use_kernel(None)
    errs = {name: _allclose(f"update/{name}", getattr(out[True], name),
                            getattr(out[False], name), **UPDATE_TOL)
            for name in ("entity", "ent_gsq", "r_emb", "rel_gsq")}
    moved = float(np.max(np.abs(np.asarray(out[True].entity)
                                - np.asarray(state.entity))))
    if not moved > 0:
        raise AssertionError("update: the fused step left the table as it was")
    print("[update] fused vs jnp on the same gradients, max |diff|: "
          + ", ".join(f"{k} {v:.3g}" for k, v in errs.items())
          + f" (tolerance {UPDATE_TOL})")


def phase_kge_score(compile_log):
    import jax
    import jax.numpy as jnp

    from repro.core.scores import pairwise_scores
    from repro.kernels.kge_score.ops import kernel_pairwise_fn

    ko, kn, kw = jax.random.split(jax.random.key(1), 3)
    o = jax.random.normal(ko, (64, 400), jnp.float32)
    negs = jax.random.normal(kn, (32, 400), jnp.float32)
    w = jax.random.normal(kw, (64, 32), jnp.float32)
    # the MXU modes run f32 matmuls at the chip's default precision, as the
    # jnp reference does; L1 is exact f32 on the VPU
    tols = {"l2sq": dict(rtol=2e-2, atol=2.0), "l1": dict(rtol=1e-4, atol=1e-2)}
    for mode, tol in tols.items():
        def loss(fn, o_, n_):
            return jnp.sum(w * fn(mode, o_, n_))

        got = jax.jit(kernel_pairwise_fn, static_argnums=0)(mode, o, negs)
        want = jax.jit(pairwise_scores, static_argnums=0)(mode, o, negs)
        e = _allclose(f"kge_score/{mode}", got, want, **tol)
        g_k = jax.jit(jax.grad(functools.partial(loss, kernel_pairwise_fn),
                               argnums=(0, 1)))(o, negs)
        g_r = jax.jit(jax.grad(functools.partial(loss, pairwise_scores),
                               argnums=(0, 1)))(o, negs)
        eg = max(_allclose(f"kge_score/{mode}/d{x}", a, b, **tol)
                 for x, a, b in zip("on", g_k, g_r))
        print(f"[kge_score] {mode}: kernel vs jnp max |diff| scores {e:.3g}, "
              f"grads {eg:.3g} (tolerance {tol})")
    for model in ("transe_l2", "transe_l1"):
        _train(f"kge_score/{model}",
               ["--dataset", "fb15k", "--scale", "1.0", "--model", model,
                "--use-kernel"], KERNEL_STEPS, compile_log)


def phase_four_chips(compile_log):
    from repro.launch.engine import TelemetryHook

    base = ["--dataset", "fb15k", "--scale", "1.0", "--model", "transe_l2",
            "--distributed"]
    counters = ("kvstore/pull_rows", "kvstore/pull_bytes",
                "kvstore/push_rows", "kvstore/push_bytes")
    for mesh in ("1x1", "4x1"):
        before = {c: _counter(c) for c in counters}
        state = _train(f"mesh {mesh}", base + ["--mesh", mesh], STEPS,
                       compile_log, extra_hooks=[TelemetryHook(every=STEPS)])
        delta = {c: _counter(c) - before[c] for c in counters}
        devices = state["entity"].sharding.device_set
        shards = state["entity"].addressable_shards
        print(f"[mesh {mesh}] entity {tuple(state['entity'].shape)} over "
              f"{len(devices)} device(s), shard {tuple(shards[0].data.shape)}; "
              + ", ".join(f"{c}={v:g}" for c, v in delta.items()))
        if mesh == "4x1":
            if len(devices) != 4 or len({s.device for s in shards}) != 4:
                raise AssertionError("entity table is not spread over 4 devices")
            zero = [c for c, v in delta.items() if not v > 0]
            if zero:
                raise AssertionError(f"KVStore counters did not count: {zero}")


# ----------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the partitioned KVStore path on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.common import telemetry
    from repro.common.compile_cache import use_compile_cache

    cache = use_compile_cache()
    compile_log = CompileLog()
    telemetry.enable()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(compile_log)
    else:
        phase_update(phase_train(compile_log))
        phase_kge_score(compile_log)
    print(f"peak_bytes_in_use {_peak_bytes(dev)}; total "
          f"{time.perf_counter() - t0:.1f} s; {compile_log.line(0.0)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
