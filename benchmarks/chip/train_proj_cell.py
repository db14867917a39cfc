"""Entry ``train_proj``: the ``train`` entry's loop for a model with
per-relation projections (TransR), timed.

As ``train_cell.py``, with the projection table among the tables whose
first-gradient norm and change are compared, ``reference_transr.py`` as the
reference, and the unique relation rows counted for the projection's bytes
as well. The cell reads the time of the program's projected-scoring kernels
(``kge.transr_score``): a program without them (no
``repro.kernels.kge_score.transr_l2sq``) cannot be measured here, and the
entry stops before any work.

Set-up builds one compiled step and one state the way the program's trainer
does (``init_state``, ``make_train_step``, ``JointSampler`` feeding
``batch_to_device``, ``launch.engine.train_loop``) and drives it from the
seed through the first steps, which warm up every shape and are compared
with the reference; the batches the sampler fed them are checked on their
own (``check.batch_numbers``). The window then continues the same loop on
the same state until ``--seconds`` have passed, syncing on the loss every
``sync_every`` steps as the program's logging does, and ends at a step
boundary with ``block_until_ready``.
"""

from __future__ import annotations

import time

import numpy as np

import check
import reference_transr as reference


class _WindowEnd(Exception):
    pass


def run(cell: dict, graph, seed: int, seconds: float, bench) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.kernels.kge_score import transr_l2sq  # noqa: F401  (see above)
    from repro.common.config import KGEConfig
    from repro.core.kge_model import batch_to_device, init_state, make_train_step
    from repro.core.sampling import JointSampler
    from repro.launch.engine import Hook, train_loop

    traffic = cell["traffic"]
    kge = dict(cell["config"]["kge"], **traffic.get("overrides", {}))
    cfg = KGEConfig(**kge)
    n_ref = traffic["check_steps"]

    state = init_state(cfg, jax.random.key(seed), overlap=cfg.overlap_update)
    start = {"entity": jnp.copy(state.entity), "rel": jnp.copy(state.r_emb),
             "proj": jnp.copy(state.r_proj)}
    # a deferred (T5) entity update lands one step later than the relations'
    lag = {"entity": 1 if state.pend_ids is not None else 0, "rel": 0, "proj": 0}
    step = make_train_step(cfg)
    sampler = JointSampler(graph.train, cfg.n_entities, cfg, np.random.default_rng(seed))

    fed = []  # host copies of the batches the reference replays
    uniques = []  # (entity rows, relation rows) per batch fed while counting
    counting = [False]

    def feed():
        b = sampler.sample()
        if len(fed) < n_ref:
            fed.append({"h": b.h.copy(), "r": b.r.copy(), "t": b.t.copy(),
                        "neg": b.neg.copy()})
        if counting[0]:
            uniques.append((np.unique(np.concatenate([b.h, b.t, b.neg.ravel()])).size,
                            np.unique(b.r).size))
        return batch_to_device(b), None

    prog = {"loss": [], "grad_norm": {}, "change_norm": {}}
    tables = {"entity": ("entity", "ent_gsq"), "rel": ("r_emb", "rel_gsq"),
              "proj": ("r_proj", "proj_gsq")}

    class Readings(Hook):
        def on_step(self, i, st, metrics, stats):
            if i <= n_ref:
                prog["loss"].append(float(metrics["loss"]))
            for k, (tab, acc) in tables.items():
                if i == 1 + lag[k]:
                    prog["grad_norm"][k] = float(jnp.sqrt(jnp.sum(getattr(st, acc))))
                if i == n_ref + lag[k]:
                    prog["change_norm"][k] = float(
                        jnp.linalg.norm(getattr(st, tab) - start[k]))

    n_warm = n_ref + max(lag.values())
    state = train_loop(step, state, feed, n_warm, hooks=[Readings()])
    jax.block_until_ready(state)
    del start

    every = traffic["sync_every"]

    class Window(Hook):
        def __init__(self, deadline):
            self.deadline = deadline
            self.last = (n_warm, state)

        def on_step(self, i, st, metrics, stats):
            self.last = (i, st)
            if i % every == 0:
                float(metrics["loss"])
                if time.perf_counter() >= self.deadline:
                    raise _WindowEnd

    def window(t0):
        hook = Window(t0 + seconds)
        try:
            train_loop(step, state, feed, 1 << 62, start=n_warm, hooks=[hook])
        except _WindowEnd:
            pass
        i, st = hook.last
        jax.block_until_ready(st)
        return i - n_warm, st

    counting[0] = bench.trace
    (steps, state), elapsed = bench.timed(window)
    bench.info(steps=steps, batch_size=cfg.batch_size)
    bench.read_memory()
    del state, step

    ref = reference.train(kge, seed, fed[:n_ref])
    numbers = dict(check.train_numbers(prog, ref),
                   **check.batch_numbers(fed[:n_ref], graph.train, kge))
    bench.info(program=prog, reference=ref)
    ent_u, rel_u = (np.mean([u[i] for u in uniques]) if uniques else None for i in (0, 1))
    return {
        "numbers": numbers,
        "attempted": steps,
        "failed": 0,
        "metrics": {"triplets_per_s": steps * cfg.batch_size / elapsed},
        "layer": {"kge": kge, "steps": steps, "seconds": elapsed,
                  # a relation's projection row is touched with its relation
                  "unique_rows": {"entity": ent_u, "rel": rel_u, "proj": rel_u}},
        "readings": {"program": prog, "reference": ref, "batches": fed[:n_ref],
                     "train": graph.train},
    }
