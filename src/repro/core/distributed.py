"""Distributed KGE training on a TPU mesh — the paper's cluster path.

Mesh layout (see DESIGN.md §4):
  machine axis ('data', or ('pod','data') multi-pod)  ≙ DGL-KE machines,
        each holding one METIS partition of entities + its relation partition;
  'model' axis                                        ≙ KVStore servers inside
        a machine: every table row is dim-striped across them.

One train step, entirely inside ``compat.shard_map``:

  1. pull: local entity rows (shared-memory fast path, 0 ICI) + remote rows
     via capacity-bounded all_to_all (embeddings/kvstore.py); relations the
     same way; split ("shared") relations read from a small replicated table.
  2. compute: joint-negative scores (paper T1) — pairwise GEMMs over the
     dim slice, finished by a psum over 'model'; loss; grads w.r.t. the
     pulled workspace rows ONLY (sparse, paper §2).
  3. push: local rows updated in place with sparse Adagrad; remote-row grads
     returned to owners by the reverse all_to_all; shared-relation grads
     psum'd over machines (tiny). Entity updates can be deferred one step
     (paper T5 "overlap gradient update with batch processing").

The batch buffers come from core/sampling.DistSampler (fixed shapes, -1 pads).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common import compat, telemetry
from repro.common.config import KGEConfig
from repro.core import scores as S
from repro.core.sampling import MODES
from repro.core.step import (
    prefetch_workspaces,
    store_pipelined_step,
    store_train_step,
)
from repro.embeddings.kvstore import KVStoreSpec
from repro.embeddings.store import ReplicatedStore, ShardedIds, ShardedStore
from repro.embeddings.table import emb_init_scale


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistKGEState:
    """All tables are (n_parts * rows_per_part, width), machine×model sharded.
    ``pending_*`` hold the deferred entity update (T5); zero-size when off."""

    entity: jnp.ndarray
    ent_gsq: jnp.ndarray
    r_emb: jnp.ndarray
    rel_gsq: jnp.ndarray
    r_proj: Optional[jnp.ndarray]
    proj_gsq: Optional[jnp.ndarray]
    shared_rel: jnp.ndarray  # (n_shared_pad, rel_dim) replicated over machines
    shared_gsq: jnp.ndarray
    pend_ids: jnp.ndarray  # (P, Lp) machine-local row ids, -1 pad
    pend_grads: jnp.ndarray  # (P, Lp, d)
    step: jnp.ndarray


def machine_axis_of(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def n_machines(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in machine_axis_of(mesh)]))


@dataclasses.dataclass(frozen=True)
class DistKGEProgram:
    """Shapes + shardings for one (cfg, mesh) pair; builds the jitted step."""

    cfg: KGEConfig
    rows_per_part: int  # entity rows per machine
    rel_slots: int  # owned relation slots per machine
    n_shared: int  # shared (split) relations, padded
    L: int  # entity workspace local slots
    Rp: int  # remote entity rows per peer
    Lr: int
    Rrp: int
    # --pipeline-depth: 1 = double-buffered pull prefetch (the state carries
    # next-step workspaces; the pull for batch t+1 issues before the push of
    # batch t). 0 = the eager step, bit-identical to build_dist_train_step.
    pipeline_depth: int = 0
    # --push-every K: remote grads coalesce in per-peer merge buffers for K
    # steps and leave in one deduplicated all_to_all (push_flush program)
    push_every: int = 1

    def state_shapes(self) -> Dict[str, jax.ShapeDtypeStruct]:
        cfg = self.cfg
        P_ = cfg.n_parts
        f32 = jnp.float32
        ent = (P_ * self.rows_per_part, cfg.dim)
        rel = (P_ * self.rel_slots, cfg.rel_dim)
        out = {
            "entity": jax.ShapeDtypeStruct(ent, f32),
            "ent_gsq": jax.ShapeDtypeStruct(ent, f32),
            "r_emb": jax.ShapeDtypeStruct(rel, f32),
            "rel_gsq": jax.ShapeDtypeStruct(rel, f32),
            "shared_rel": jax.ShapeDtypeStruct((self.n_shared, cfg.rel_dim), f32),
            "shared_gsq": jax.ShapeDtypeStruct((self.n_shared, cfg.rel_dim), f32),
            "pend_ids": jax.ShapeDtypeStruct((P_, self.pend_slots), jnp.int32),
            "pend_grads": jax.ShapeDtypeStruct((P_, self.pend_slots, cfg.dim), f32),
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }
        if cfg.model in ("transr", "rescal"):
            proj = (P_ * self.rel_slots, cfg.dim * cfg.rel_dim)
            out["r_proj"] = jax.ShapeDtypeStruct(proj, f32)
            out["proj_gsq"] = jax.ShapeDtypeStruct(proj, f32)
        if self.pipeline_depth:
            # the double buffer: next-step entity/relation workspaces, pulled
            # by the previous step (or the prime program at step 0)
            out["pf_ent_ws"] = jax.ShapeDtypeStruct(
                (P_, self.L + P_ * self.Rp, cfg.dim), f32)
            out["pf_rel_ws"] = jax.ShapeDtypeStruct(
                (P_, self.Lr + P_ * self.Rrp, cfg.rel_dim), f32)
        if self.push_every > 1:
            ck = self.coalesce_slots
            out["co_ids"] = jax.ShapeDtypeStruct((P_, P_, ck), jnp.int32)
            out["co_grads"] = jax.ShapeDtypeStruct((P_, P_, ck, cfg.dim), f32)
        return out

    @property
    def pend_slots(self) -> int:
        # deferred update rows: all local slots + all remote arrivals
        return self.L + self.cfg.n_parts * self.Rp

    @property
    def coalesce_slots(self) -> int:
        """Per-peer merge-buffer capacity Ck for --push-every K.

        Ck = max(Rp, K*Rp // 2): half the worst-case unique rows of K steps,
        never below one step's capacity. The flush then moves at most
        P * Ck = K*Rp*P / 2 row-slots per K steps vs the eager K*Rp*P — a
        guaranteed >= 2x reduction in push rows/bytes (for K >= 2; skewed
        access patterns dedup further below the cap). Overflow drops are
        counted (``push_dropped``), never silent.
        """
        if self.push_every <= 1:
            return 0
        return max(self.Rp, (self.push_every * self.Rp) // 2)

    def batch_shapes(self) -> Dict[str, jax.ShapeDtypeStruct]:
        cfg = self.cfg
        P_, b = cfg.n_parts, cfg.batch_size
        i32 = jnp.int32
        ng, k = cfg.n_neg_groups, cfg.neg_sample_size
        return {
            "ent_local_ids": jax.ShapeDtypeStruct((P_, self.L), i32),
            "ent_remote_req": jax.ShapeDtypeStruct((P_, P_, self.Rp), i32),
            "h_slot": jax.ShapeDtypeStruct((P_, b), i32),
            "t_slot": jax.ShapeDtypeStruct((P_, b), i32),
            "neg_slot": jax.ShapeDtypeStruct((P_, MODES, ng, k), i32),
            "rel_local_ids": jax.ShapeDtypeStruct((P_, self.Lr), i32),
            "rel_remote_req": jax.ShapeDtypeStruct((P_, P_, self.Rrp), i32),
            "rel_slot": jax.ShapeDtypeStruct((P_, b), i32),
            "rel_shared": jax.ShapeDtypeStruct((P_, b), i32),
        }


def make_program(cfg: KGEConfig, rows_per_part: int, rel_slots: int,
                 n_shared: int, pipeline_depth: int = 0,
                 push_every: int = 1) -> DistKGEProgram:
    if pipeline_depth not in (0, 1):
        raise ValueError(f"pipeline_depth must be 0 or 1, got {pipeline_depth}")
    if push_every < 1:
        raise ValueError(f"push_every must be >= 1, got {push_every}")
    if pipeline_depth and cfg.model in ("transr", "rescal"):
        raise ValueError(
            f"pipeline_depth=1 does not support model={cfg.model!r}: the "
            "double buffer carries entity/relation workspaces only (no "
            "projection-matrix prefetch slot)")
    if (pipeline_depth or push_every > 1) and cfg.overlap_update:
        raise ValueError(
            "pipelined pull prefetch / coalesced push and overlap_update "
            "(T5 defer) are mutually exclusive: both are single-writer "
            "one-step-stale overlap mechanisms over the same pend state")
    k = cfg.neg_sample_size
    L = 3 * cfg.batch_size + MODES * cfg.n_neg_groups * k
    Rp = max(1, cfg.remote_capacity // cfg.n_parts)
    Lr = cfg.batch_size
    Rrp = max(1, max(8, cfg.remote_capacity // 8) // cfg.n_parts)
    return DistKGEProgram(
        cfg=cfg, rows_per_part=rows_per_part, rel_slots=rel_slots,
        n_shared=max(8, n_shared), L=L, Rp=Rp, Lr=Lr, Rrp=Rrp,
        pipeline_depth=pipeline_depth, push_every=push_every,
    )


# ---------------------------------------------------------------------------
def stores_from_dist_state(cfg: KGEConfig, state: Dict, spec: KVStoreSpec,
                           machine_axis) -> Dict[str, object]:
    """View one machine's state-dict block as EmbeddingStores.

    Tensors must already be machine-local (inside shard_map, or a whole
    n_parts == 1 state with ``machine_axis=None``). ``pend_ids``/``pend_grads``
    must be squeezed of the machine axis.

    T5 note: the entity store defers when cfg.overlap_update, and its
    ``flush()`` (run at the top of the next step) reads the POST-update
    table. Reading the pre-update table (the literal paper semantics) forces
    XLA into a copy-on-write of the full entity + Adagrad tables — a
    2.2 GB/step HBM tax at Freebase scale (EXPERIMENTS.md §Perf hillclimb 3).
    Reading post-update keeps the one-step deferral of gradient application
    (the overlap) with *fresher* rows, and the scatter becomes a true
    in-place update.
    """
    ent_kw = {}
    if "co_ids" in state:
        # --push-every: the entity store coalesces remote grads into the
        # state-carried per-peer merge buffers (also machine-axis squeezed)
        ent_kw = dict(co_ids=state["co_ids"], co_grads=state["co_grads"],
                      coalesce=True)
    stores = {
        "entity": ShardedStore(
            state["entity"], state["ent_gsq"],
            state["pend_ids"], state["pend_grads"],
            spec=spec, lr=cfg.lr, defer=cfg.overlap_update, **ent_kw),
        # relations are never deferred (paper: trainer-immediate)
        "rel": ShardedStore(
            state["r_emb"], state["rel_gsq"],
            jnp.zeros((0,), jnp.int32), jnp.zeros((0, cfg.rel_dim)),
            spec=spec, lr=cfg.lr, defer=False),
        "shared": ReplicatedStore(
            state["shared_rel"], state["shared_gsq"],
            lr=cfg.lr, machine_axis=machine_axis),
    }
    if "r_proj" in state:
        stores["proj"] = ShardedStore(
            state["r_proj"], state["proj_gsq"],
            jnp.zeros((0,), jnp.int32),
            jnp.zeros((0, cfg.dim * cfg.rel_dim)),
            spec=spec, lr=cfg.lr, defer=False)
    return stores


def _device_step(prog: DistKGEProgram, machine_axis, state: Dict, batch: Dict,
                 pairwise_fn=None, n_servers: int = 1):
    """Per-device body (inside shard_map). All tensors are local blocks:
    entity (rows_per_part, d/S), batch arrays squeezed of the machine axis."""
    cfg = prog.cfg
    spec = KVStoreSpec(machine_axis=machine_axis, n_parts=cfg.n_parts,
                       remote_capacity=cfg.remote_capacity,
                       comm_dtype=cfg.comm_dtype)
    sq = lambda x: jnp.squeeze(x, axis=0)  # drop size-1 machine axis

    local_state = dict(state)
    local_state["pend_ids"] = sq(state["pend_ids"])
    local_state["pend_grads"] = sq(state["pend_grads"])
    if "co_ids" in state:
        local_state["co_ids"] = sq(state["co_ids"])
        local_state["co_grads"] = sq(state["co_grads"])
    stores = stores_from_dist_state(cfg, local_state, spec, machine_axis)
    step_batch = {
        "ent_ids": ShardedIds(sq(batch["ent_local_ids"]),
                              sq(batch["ent_remote_req"])),
        "rel_ids": ShardedIds(sq(batch["rel_local_ids"]),
                              sq(batch["rel_remote_req"])),
        "h_slot": sq(batch["h_slot"]),
        "t_slot": sq(batch["t_slot"]),
        "neg_slot": sq(batch["neg_slot"]),
        "rel_slot": sq(batch["rel_slot"]),
        "rel_shared": sq(batch["rel_shared"]),
    }

    new_stores, metrics = store_train_step(
        cfg, stores, step_batch, ctx=S.ShardCtx("model"),
        n_servers=n_servers, machine_axis=machine_axis,
        pairwise_fn=pairwise_fn)

    ent, rel = new_stores["entity"], new_stores["rel"]
    shared = new_stores["shared"]
    out = dict(state)
    out.update(
        entity=ent.table, ent_gsq=ent.gsq, r_emb=rel.table, rel_gsq=rel.gsq,
        shared_rel=shared.table, shared_gsq=shared.gsq,
        pend_ids=ent.pend_ids[None], pend_grads=ent.pend_grads[None],
        step=state["step"] + 1,
    )
    if "r_proj" in state:
        out["r_proj"] = new_stores["proj"].table
        out["proj_gsq"] = new_stores["proj"].gsq
    if "co_ids" in state:
        out["co_ids"] = ent.co_ids[None]
        out["co_grads"] = ent.co_grads[None]
    return out, metrics


def _batch_addresses(prog: DistKGEProgram, batch: Dict, sq) -> Dict:
    """The pull addresses of one (machine-axis squeezed) batch."""
    del prog
    return {
        "ent_ids": ShardedIds(sq(batch["ent_local_ids"]),
                              sq(batch["ent_remote_req"])),
        "rel_ids": ShardedIds(sq(batch["rel_local_ids"]),
                              sq(batch["rel_remote_req"])),
    }


def _device_prime(prog: DistKGEProgram, machine_axis, state: Dict, batch: Dict):
    """Fill the pipeline's double buffer for the FIRST batch (depth-1 step 0
    has no previous step to have prefetched it)."""
    cfg = prog.cfg
    spec = KVStoreSpec(machine_axis=machine_axis, n_parts=cfg.n_parts,
                       remote_capacity=cfg.remote_capacity,
                       comm_dtype=cfg.comm_dtype)
    sq = lambda x: jnp.squeeze(x, axis=0)
    local_state = dict(state)
    local_state["pend_ids"] = sq(state["pend_ids"])
    local_state["pend_grads"] = sq(state["pend_grads"])
    if "co_ids" in state:
        local_state["co_ids"] = sq(state["co_ids"])
        local_state["co_grads"] = sq(state["co_grads"])
    stores = stores_from_dist_state(cfg, local_state, spec, machine_axis)
    pf = prefetch_workspaces(stores, _batch_addresses(prog, batch, sq))
    out = dict(state)
    out["pf_ent_ws"] = pf["entity"][None]
    out["pf_rel_ws"] = pf["rel"][None]
    return out


def _device_step_pipelined(prog: DistKGEProgram, machine_axis, state: Dict,
                           batch: Dict, next_batch: Dict,
                           pairwise_fn=None, n_servers: int = 1):
    """Depth-1 per-device body: grads against the state-carried prefetched
    workspaces, then the pull for ``next_batch`` in program order BEFORE the
    push/apply of ``batch`` (core/step.store_pipelined_step)."""
    cfg = prog.cfg
    spec = KVStoreSpec(machine_axis=machine_axis, n_parts=cfg.n_parts,
                       remote_capacity=cfg.remote_capacity,
                       comm_dtype=cfg.comm_dtype)
    sq = lambda x: jnp.squeeze(x, axis=0)

    local_state = dict(state)
    local_state["pend_ids"] = sq(state["pend_ids"])
    local_state["pend_grads"] = sq(state["pend_grads"])
    if "co_ids" in state:
        local_state["co_ids"] = sq(state["co_ids"])
        local_state["co_grads"] = sq(state["co_grads"])
    stores = stores_from_dist_state(cfg, local_state, spec, machine_axis)
    step_batch = {
        "h_slot": sq(batch["h_slot"]),
        "t_slot": sq(batch["t_slot"]),
        "neg_slot": sq(batch["neg_slot"]),
        "rel_slot": sq(batch["rel_slot"]),
        "rel_shared": sq(batch["rel_shared"]),
        **_batch_addresses(prog, batch, sq),
    }
    prefetched = {"entity": sq(state["pf_ent_ws"]),
                  "rel": sq(state["pf_rel_ws"])}

    new_stores, new_pf, metrics = store_pipelined_step(
        cfg, stores, step_batch, prefetched,
        _batch_addresses(prog, next_batch, sq),
        ctx=S.ShardCtx("model"), n_servers=n_servers,
        machine_axis=machine_axis, pairwise_fn=pairwise_fn)

    ent, rel = new_stores["entity"], new_stores["rel"]
    shared = new_stores["shared"]
    out = dict(state)
    out.update(
        entity=ent.table, ent_gsq=ent.gsq, r_emb=rel.table, rel_gsq=rel.gsq,
        shared_rel=shared.table, shared_gsq=shared.gsq,
        pend_ids=ent.pend_ids[None], pend_grads=ent.pend_grads[None],
        pf_ent_ws=new_pf["entity"][None], pf_rel_ws=new_pf["rel"][None],
        step=state["step"] + 1,
    )
    if "co_ids" in state:
        out["co_ids"] = ent.co_ids[None]
        out["co_grads"] = ent.co_grads[None]
    return out, metrics


def _device_push_flush(prog: DistKGEProgram, machine_axis, state: Dict):
    """Per-device body of the coalesced-push flush program: ONE deduplicated
    all_to_all returns K steps' remote grads to owners, owners apply."""
    cfg = prog.cfg
    spec = KVStoreSpec(machine_axis=machine_axis, n_parts=cfg.n_parts,
                       remote_capacity=cfg.remote_capacity,
                       comm_dtype=cfg.comm_dtype)
    sq = lambda x: jnp.squeeze(x, axis=0)
    ent = ShardedStore(
        state["entity"], state["ent_gsq"],
        jnp.zeros((0,), jnp.int32), jnp.zeros((0, cfg.dim)),
        spec=spec, lr=cfg.lr, defer=False,
        co_ids=sq(state["co_ids"]), co_grads=sq(state["co_grads"]),
        coalesce=True).push_flush()
    out = dict(state)
    out.update(entity=ent.table, ent_gsq=ent.gsq,
               co_ids=ent.co_ids[None], co_grads=ent.co_grads[None])
    return out


def _program_specs(prog: DistKGEProgram, maxis):
    """PartitionSpecs for (state, batch, metrics) of one DistKGEProgram."""
    cfg = prog.cfg
    mp = P(maxis, "model")  # machine-row × dim-striped tables
    state_specs = {
        "entity": mp, "ent_gsq": mp, "r_emb": mp, "rel_gsq": mp,
        "shared_rel": P(None, "model"), "shared_gsq": P(None, "model"),
        "pend_ids": P(maxis, None), "pend_grads": P(maxis, None, "model"),
        "step": P(),
    }
    if cfg.model in ("transr", "rescal"):
        state_specs["r_proj"] = mp
        state_specs["proj_gsq"] = mp
    if prog.pipeline_depth:
        state_specs["pf_ent_ws"] = P(maxis, None, "model")
        state_specs["pf_rel_ws"] = P(maxis, None, "model")
    if prog.push_every > 1:
        state_specs["co_ids"] = P(maxis, None, None)
        state_specs["co_grads"] = P(maxis, None, None, "model")
    batch_specs = {
        "ent_local_ids": P(maxis, None),
        "ent_remote_req": P(maxis, None, None),
        "h_slot": P(maxis, None),
        "t_slot": P(maxis, None),
        "neg_slot": P(maxis, None, None, None),
        "rel_local_ids": P(maxis, None),
        "rel_remote_req": P(maxis, None, None),
        "rel_slot": P(maxis, None),
        "rel_shared": P(maxis, None),
    }
    metric_specs = {"loss": P(), "pos_score": P(), "neg_score": P()}
    if cfg.overlap_update:
        # store_train_step adds the T5 defer drop-count metric when the
        # entity store defers (same static condition as the store build)
        metric_specs["pend_dropped"] = P()
    if prog.push_every > 1:
        metric_specs["push_dropped"] = P()
    return state_specs, batch_specs, metric_specs


def build_dist_train_step(prog: DistKGEProgram, mesh: Mesh, pairwise_fn=None):
    """Returns jit(train_step)(state_dict, batch_dict) -> (state_dict, metrics)."""
    cfg = prog.cfg
    maxis = machine_axis_of(mesh)
    assert n_machines(mesh) == cfg.n_parts, (
        f"cfg.n_parts={cfg.n_parts} must equal machine-axis size {n_machines(mesh)}")

    state_specs, batch_specs, metric_specs = _program_specs(prog, maxis)
    body = functools.partial(_device_step, prog, maxis, pairwise_fn=pairwise_fn,
                             n_servers=int(mesh.shape["model"]))
    smapped = compat.shard_map(
        body, mesh=mesh,
        in_specs=(state_specs, batch_specs),
        out_specs=(state_specs, metric_specs),
        check_vma=False,
    )
    state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs,
                            is_leaf=lambda x: isinstance(x, P))
    return jax.jit(smapped, donate_argnums=(0,)), state_sh, jax.tree.map(
        lambda s: NamedSharding(mesh, s), batch_specs,
        is_leaf=lambda x: isinstance(x, P))


class PipelinedDistStep:
    """Host-side runner around the pipelined/coalesced jitted programs.

    Call signature when ``lookahead``: ``runner(state, batch, next_batch)``
    (the train loop peeks batch t+1 from the WorkerPool without consuming
    it — data/pipeline.WorkerPool.peek); otherwise ``runner(state, batch)``
    like the eager step. ``finalize(state)`` flushes a partial coalesce
    window at loop end — launch/engine.train_loop calls it before _finish.

    Telemetry: the flush program runs once per K steps, so the per-step
    replay that TelemetryHook does for the eager step would overcount its
    trace-time statics K-fold. The runner therefore drains the statics
    itself right after each program call and replays them per *call* of the
    owning program (``_per_step`` for prime+step, ``_per_flush`` for flush);
    TelemetryHook then finds an empty buffer and double-counts nothing.
    """

    def __init__(self, step_fn, prime_fn, flush_fn, push_every: int,
                 lookahead: bool):
        self._step = step_fn
        self._prime = prime_fn
        self._flush = flush_fn
        self._k = push_every
        self.lookahead = lookahead
        self._primed = False
        self._i = 0
        self._statics: Dict[str, Dict[str, float]] = {}

    def _replay(self, program: str, per: str = "step") -> None:
        reg = telemetry.get_registry()
        if not reg.enabled:
            return
        drained = reg.drain_statics()
        if drained:
            self._statics[program] = drained
        for name, v in self._statics.get(program, {}).items():
            reg.inc(name, v)
            reg.gauge(f"{name}_per_{per}", v)

    def _run_flush(self, state):
        state = self._flush(state)
        telemetry.inc("kvstore/coalesced_push_flushes")
        self._replay("flush", per="flush")
        return state

    def __call__(self, state, batch, next_batch=None):
        if self.lookahead:
            if not self._primed:
                state = self._prime(state, batch)
                self._replay("prime")
                self._primed = True
            state, metrics = self._step(state, batch, next_batch)
        else:
            state, metrics = self._step(state, batch)
        self._replay("step")
        self._i += 1
        if self._flush is not None and self._i % self._k == 0:
            state = self._run_flush(state)
        return state, metrics

    def finalize(self, state):
        """Flush a partial coalesce window (grads must never be lost)."""
        if self._flush is not None and self._i % self._k != 0:
            state = self._run_flush(state)
        return state


def build_pipelined_dist_step(prog: DistKGEProgram, mesh: Mesh,
                              pairwise_fn=None):
    """The pipelined variant of ``build_dist_train_step``.

    Returns ``(step, state_sh, batch_sh)`` where ``step`` is a
    ``PipelinedDistStep`` runner — or the plain eager jitted step when the
    program has no pipelining at all (depth 0, push_every 1): that path is
    bit-identical to ``build_dist_train_step`` by construction.
    """
    if prog.pipeline_depth == 0 and prog.push_every == 1:
        return build_dist_train_step(prog, mesh, pairwise_fn)
    cfg = prog.cfg
    maxis = machine_axis_of(mesh)
    assert n_machines(mesh) == cfg.n_parts, (
        f"cfg.n_parts={cfg.n_parts} must equal machine-axis size {n_machines(mesh)}")
    state_specs, batch_specs, metric_specs = _program_specs(prog, maxis)
    n_srv = int(mesh.shape["model"])

    prime_fn = None
    if prog.pipeline_depth:
        body = functools.partial(_device_step_pipelined, prog, maxis,
                                 pairwise_fn=pairwise_fn, n_servers=n_srv)
        smapped = compat.shard_map(
            body, mesh=mesh,
            in_specs=(state_specs, batch_specs, batch_specs),
            out_specs=(state_specs, metric_specs), check_vma=False)
        prime = compat.shard_map(
            functools.partial(_device_prime, prog, maxis), mesh=mesh,
            in_specs=(state_specs, batch_specs),
            out_specs=state_specs, check_vma=False)
        prime_fn = jax.jit(prime, donate_argnums=(0,))
    else:
        body = functools.partial(_device_step, prog, maxis,
                                 pairwise_fn=pairwise_fn, n_servers=n_srv)
        smapped = compat.shard_map(
            body, mesh=mesh,
            in_specs=(state_specs, batch_specs),
            out_specs=(state_specs, metric_specs), check_vma=False)
    step_fn = jax.jit(smapped, donate_argnums=(0,))

    flush_fn = None
    if prog.push_every > 1:
        fmapped = compat.shard_map(
            functools.partial(_device_push_flush, prog, maxis), mesh=mesh,
            in_specs=(state_specs,), out_specs=state_specs, check_vma=False)
        flush_fn = jax.jit(fmapped, donate_argnums=(0,))

    runner = PipelinedDistStep(step_fn, prime_fn, flush_fn,
                               push_every=prog.push_every,
                               lookahead=prog.pipeline_depth > 0)
    is_spec = lambda x: isinstance(x, P)
    state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs,
                            is_leaf=is_spec)
    batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), batch_specs,
                            is_leaf=is_spec)
    return runner, state_sh, batch_sh


def init_dist_state(prog: DistKGEProgram, key: jax.Array) -> Dict[str, jnp.ndarray]:
    cfg = prog.cfg
    s = emb_init_scale(cfg)
    shapes = prog.state_shapes()
    ks = jax.random.split(key, 4)
    out = {}
    for name, sd in shapes.items():
        if name in ("entity", "r_emb", "shared_rel"):
            i = ["entity", "r_emb", "shared_rel"].index(name)
            out[name] = jax.random.uniform(ks[i], sd.shape, sd.dtype, -s, s)
        elif name == "r_proj":
            p = jax.random.uniform(ks[3], sd.shape, sd.dtype, -s, s)
            if cfg.model == "transr":
                eye = jnp.eye(cfg.dim, cfg.rel_dim, dtype=jnp.float32).reshape(-1)
                p = p * 0.1 + eye
            out[name] = p
        elif name in ("pend_ids", "co_ids"):
            out[name] = jnp.full(sd.shape, -1, sd.dtype)
        else:
            out[name] = jnp.zeros(sd.shape, sd.dtype)
    return out
