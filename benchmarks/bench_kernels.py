"""Kernel-path benchmark: the T1 GEMM reformulation's arithmetic-intensity
gain, plus jnp-path step timings with/without the joint form, plus the
fused sparse-Adagrad kernel's memory-traffic advantage.

Pallas interpret-mode wall-clock on CPU is not meaningful (it is an
emulator); the TPU-relevant quantity is the memory-traffic ratio, which is
shape-derived, and the XLA-fused jnp path timing, which the op-efficiency
claims map onto. ``run_sparse_adagrad`` records its comparison into
``BENCH_sparse_adagrad.json`` at the repo root."""

from __future__ import annotations

import json
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_loop
from repro.common import compat, telemetry
from repro.core.scores import pairwise_scores
from repro.kernels.sparse_adagrad.ops import _tile_rows
from repro.optim.sparse_adagrad import sparse_adagrad_apply, use_kernel


def run():
    rng = np.random.default_rng(0)
    b, k, d = 1024, 256, 400
    o = jnp.asarray(rng.standard_normal((b, d)).astype(np.float32))
    negs = jnp.asarray(rng.standard_normal((k, d)).astype(np.float32))

    gemm = jax.jit(lambda a, n: pairwise_scores("l2sq", a, n))
    t_gemm = time_loop(lambda: gemm(o, negs), iters=20)

    # the pre-T1 form: per-triplet negatives, no shared pool -> (b, k, d)
    negs_full = jnp.asarray(rng.standard_normal((b, k, d)).astype(np.float32))
    naive = jax.jit(lambda a, n: jnp.sum(jnp.square(a[:, None, :] - n), -1))
    t_naive = time_loop(lambda: naive(o, negs_full), iters=20)

    bytes_joint = (b * d + k * d + b * k) * 4
    bytes_naive = (b * d + b * k * d + b * k) * 4
    emit("kernel/joint_gemm_l2sq", t_gemm,
         f"speedup={t_naive/t_gemm:.1f}x bytes_ratio={bytes_naive/bytes_joint:.0f}x "
         f"flops/byte={2*b*k*d/bytes_joint:.1f}")
    emit("kernel/naive_pairwise", t_naive,
         f"flops/byte={2*b*k*d/bytes_naive:.2f} (memory-bound by construction)")


def run_sparse_adagrad():
    """Fused sparse-Adagrad kernel vs the jnp sort/segment/scatter path.

    Wall-clock rows/s is the jnp path (the one that runs on this backend);
    the fused kernel's number is its analytic HBM traffic — the grouping
    scatter-adds the workspace into per-tile gradient rows, the update makes
    ONE pass over the touched tiles with table/gsq aliased in place —
    against the XLA-measured bytes of the compiled jnp update (which
    rewrites the full table unless XLA can alias).
    """
    fast = os.environ.get("BENCH_FAST", "1") != "0"
    N, D, n = (50_000, 256, 4096) if fast else (500_000, 400, 16_384)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((N, D)).astype(np.float32))
    gsq = jnp.asarray(np.abs(rng.standard_normal((N, D))).astype(np.float32))
    ids_np = rng.integers(-1, N, size=n).astype(np.int32)
    ids = jnp.asarray(ids_np)
    grads = jnp.asarray(rng.standard_normal((n, D)).astype(np.float32))

    jnp_fn = jax.jit(lambda t, q, i, g: sparse_adagrad_apply(
        t, q, i, g, 0.1, use_kernel=False))
    t_jnp = time_loop(lambda: jnp_fn(table, gsq, ids, grads), iters=10)
    rows_s = n / (t_jnp / 1e6)

    compiled = jnp_fn.lower(table, gsq, ids, grads).compile()
    cost = compat.cost_analysis(compiled)
    bytes_jnp = float(cost.get("bytes accessed", 0.0))

    itm = 4  # f32
    u = len({int(i) for i in ids_np if i >= 0})
    tr = _tile_rows(jnp.float32)
    u_tiles = len({int(i) // tr for i in ids_np if i >= 0})
    # grouping: read the n workspace rows, scatter-add them into the
    # grad-tile rows; fused update: read those rows + the touched (table,
    # gsq) tiles, write the tiles back — the other tiles never move.
    bytes_fused = (n * D + 2 * u_tiles * tr * D + 4 * u_tiles * tr * D) * itm
    # jnp lower bound if XLA aliased perfectly: sort+segment (≈3 workspace
    # passes) + gather/scatter of touched rows (gsq twice: add then re-gather)
    bytes_jnp_alias = (3 * n * D + 6 * u * D) * itm
    # worst case (no aliasing): both full tables copied through HBM
    bytes_jnp_copy = bytes_jnp_alias + 4 * N * D * itm
    measured = bytes_jnp or float(bytes_jnp_copy)
    ratio = measured / bytes_fused

    emit("kernel/sparse_adagrad_jnp", t_jnp,
         f"rows/s={rows_s:.0f} bytes={measured:.3g}")
    t_fused = float("nan")
    if use_kernel():
        # a real accelerator backend: time the fused kernel for real
        fused_fn = jax.jit(lambda t, q, i, g: sparse_adagrad_apply(
            t, q, i, g, 0.1, use_kernel=True))
        t_fused = time_loop(lambda: fused_fn(table, gsq, ids, grads), iters=10)
        emit("kernel/sparse_adagrad_fused", t_fused,
             f"analytic_bytes={bytes_fused:.3g} bytes_ratio={ratio:.1f}x")
    else:
        # interpret-mode wall-clock is an emulator number, not a result:
        # print the analytic row but keep it out of the telemetry snapshot
        # (a 0.0 µs gauge here used to read as an infinitely fast kernel)
        emit("kernel/sparse_adagrad_fused", t_fused,
             f"analytic_bytes={bytes_fused:.3g} bytes_ratio={ratio:.1f}x "
             f"(fused kernel unavailable on this backend; not timed)",
             gauge=False)

    # one flat gauge per number, snapshot schema shared with --metrics-out
    # (docs/TELEMETRY.md); a dedicated registry so a concurrently-enabled
    # process registry doesn't leak unrelated metrics into the file
    reg = telemetry.MetricsRegistry(enabled=True)
    fused_row = ({"fused_us_per_call": t_fused}
                 if not np.isnan(t_fused) else {})
    for key, val in {
        **fused_row,
        "jnp_us_per_call": t_jnp,
        "jnp_rows_per_s": rows_s,
        "jnp_hbm_bytes_measured": bytes_jnp,
        "jnp_hbm_bytes_analytic_aliased": bytes_jnp_alias,
        "jnp_hbm_bytes_analytic_copy": bytes_jnp_copy,
        "fused_hbm_bytes_analytic": bytes_fused,
        "fused_vs_jnp_bytes_ratio": ratio,
        "fused_vs_jnp_bytes_ratio_aliased_lower_bound":
            bytes_jnp_alias / bytes_fused,
    }.items():
        reg.gauge(f"bench/sparse_adagrad/{key}", val)
    out = reg.snapshot(
        shape={"n_rows": N, "dim": D, "batch_ids": n, "unique_ids": u},
        note="Pallas interpret-mode wall-clock on CPU is an emulator; "
             "the TPU-relevant comparison is HBM traffic. ratio > 1 "
             "means the fused kernel moves fewer bytes per step.")
    root = pathlib.Path(__file__).resolve().parent.parent
    (root / "BENCH_sparse_adagrad.json").write_text(
        json.dumps(out, indent=2) + "\n")
