"""Paper Figs. 5/6: scaling with compute units.

CPU-SPMD throughput scaling over 1/2/4/8-way data parallelism (same global
batch per unit, like the paper's per-GPU batch); the pod-scale compile
rehearsal of the distributed step is ``python -m repro.launch.dryrun --kge``."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, kg_fixture, time_loop
from repro.common.compat import set_mesh
from repro.common.config import KGEConfig
from repro.core.distributed import build_dist_train_step, init_dist_state, make_program
from repro.core.graph_part import partition
from repro.core.rel_part import relation_partition
from repro.core.sampling import DistSampler
from repro.launch.mesh import make_mesh


def run():
    kg = kg_fixture("medium")
    base = None
    for n_parts in (1, 2, 4, 8):
        mesh = make_mesh((n_parts, 1), ("data", "model"))
        cfg = KGEConfig(model="transe_l2", n_entities=kg.n_entities,
                        n_relations=kg.n_relations, dim=128, batch_size=256,
                        neg_sample_size=64, lr=0.1, n_parts=n_parts,
                        remote_capacity=256)
        book = partition(kg.train, cfg.n_entities, n_parts)
        rp = relation_partition(kg.rel_counts(), n_parts)
        prog = make_program(cfg, book.rows_per_part, rp.slots_per_part,
                            rp.n_shared)
        sampler = DistSampler(kg.train, book, rp, cfg, np.random.default_rng(0))
        step, state_sh, batch_sh = build_dist_train_step(prog, mesh)
        with set_mesh(mesh):
            state = jax.device_put(init_dist_state(prog, jax.random.key(0)),
                                   state_sh)
            db = sampler.sample()
            batch = {k: jax.device_put(jnp.asarray(getattr(db, k)), batch_sh[k])
                     for k in batch_sh}

            def one():
                nonlocal state
                state, m = step(state, batch)
                return m

            t = time_loop(one, iters=6)
        triplets_s = n_parts * cfg.batch_size / (t / 1e6)
        if base is None:
            base = triplets_s
        emit(f"fig5/scaling_{n_parts}units", t,
             f"triplets/s={triplets_s:.0f} speedup={triplets_s/base:.2f}x "
             f"(ideal {n_parts}x; CPU cores are shared so sub-linear is expected)")
