"""Single-machine KGE training (the paper's many-core path, minus Hogwild).

This module is the reference implementation used by tests, benchmarks and the
CPU-trainable examples. It exercises T1/T2 (joint + in-batch negative
sampling) and — through ``DenseStore`` — sparse Adagrad row updates and the
optional T5 deferred update (``init_state(..., overlap=True)``).

The actual step logic lives in core/step.py (``store_train_step``), shared
with the distributed path in core/distributed.py; this module only adapts the
``KGEState`` container and the global-id batches of the single-machine
samplers onto the EmbeddingStore surface.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import telemetry
from repro.common.config import KGEConfig
from repro.core.sampling import MODES, KGBatch, NaiveBatch
from repro.core.step import store_apply_grads, store_grads, store_train_step
from repro.embeddings.store import DenseStore
from repro.embeddings.table import emb_init_scale


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KGEState:
    entity: jnp.ndarray  # (n_entities, d)
    ent_gsq: jnp.ndarray
    r_emb: jnp.ndarray  # (n_relations, rel_dim)
    rel_gsq: jnp.ndarray
    r_proj: Optional[jnp.ndarray]  # (n_relations, d*rel_dim) TransR/RESCAL
    proj_gsq: Optional[jnp.ndarray]
    step: jnp.ndarray
    # T5 deferred-update buffers (overlap=True); None = immediate updates
    pend_ids: Optional[jnp.ndarray] = None  # (Lp,) int32, -1 pad
    pend_grads: Optional[jnp.ndarray] = None  # (Lp, d)


def ent_workspace_slots(cfg: KGEConfig) -> int:
    """Entity rows touched by one joint batch: h + t + negatives."""
    return 2 * cfg.batch_size + MODES * cfg.n_neg_groups * cfg.neg_sample_size


def init_state(cfg: KGEConfig, key: jax.Array, overlap: bool = False) -> KGEState:
    s = emb_init_scale(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    ent = jax.random.uniform(k1, (cfg.n_entities, cfg.dim), jnp.float32, -s, s)
    rel = jax.random.uniform(k2, (cfg.n_relations, cfg.rel_dim), jnp.float32, -s, s)
    proj = None
    if cfg.model in ("transr", "rescal"):
        proj = jax.random.uniform(
            k3, (cfg.n_relations, cfg.dim * cfg.rel_dim), jnp.float32, -s, s
        )
        if cfg.model == "transr":
            eye = jnp.eye(cfg.dim, cfg.rel_dim, dtype=jnp.float32).reshape(-1)
            proj = proj * 0.1 + eye
    pend_ids = pend_grads = None
    if overlap:
        slots = ent_workspace_slots(cfg)
        pend_ids = jnp.full((slots,), -1, jnp.int32)
        pend_grads = jnp.zeros((slots, cfg.dim), jnp.float32)
    return KGEState(
        entity=ent,
        ent_gsq=jnp.zeros_like(ent),
        r_emb=rel,
        rel_gsq=jnp.zeros_like(rel),
        r_proj=proj,
        proj_gsq=None if proj is None else jnp.zeros_like(proj),
        step=jnp.zeros((), jnp.int32),
        pend_ids=pend_ids,
        pend_grads=pend_grads,
    )


# --------------------------------------------------------------------------
# KGEState <-> EmbeddingStore adapters
# --------------------------------------------------------------------------
def _empty(width: int):
    return jnp.zeros((0,), jnp.int32), jnp.zeros((0, width), jnp.float32)


def stores_from_state(cfg: KGEConfig, state: KGEState) -> Dict[str, DenseStore]:
    """View the flat KGEState as DenseStores (zero-copy; arrays are shared)."""
    defer = state.pend_ids is not None
    pid, pg = ((state.pend_ids, state.pend_grads) if defer
               else _empty(cfg.dim))
    stores = {
        "entity": DenseStore(state.entity, state.ent_gsq, pid, pg,
                             lr=cfg.lr, defer=defer),
        # relations are never deferred (paper: trainer-immediate)
        "rel": DenseStore(state.r_emb, state.rel_gsq, *_empty(cfg.rel_dim),
                          lr=cfg.lr, defer=False),
    }
    if state.r_proj is not None:
        stores["proj"] = DenseStore(state.r_proj, state.proj_gsq,
                                    *_empty(cfg.dim * cfg.rel_dim),
                                    lr=cfg.lr, defer=False)
    return stores


def state_from_stores(state: KGEState, stores: Dict[str, DenseStore]) -> KGEState:
    ent, rel = stores["entity"], stores["rel"]
    proj = stores.get("proj")
    defer = state.pend_ids is not None
    return dataclasses.replace(
        state,
        entity=ent.table, ent_gsq=ent.gsq,
        r_emb=rel.table, rel_gsq=rel.gsq,
        r_proj=None if proj is None else proj.table,
        proj_gsq=None if proj is None else proj.gsq,
        step=state.step + 1,
        pend_ids=ent.pend_ids if defer else None,
        pend_grads=ent.pend_grads if defer else None,
    )


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["ids"], meta_fields=["b", "neg_shape"])
@dataclasses.dataclass(frozen=True)
class PackedBatch:
    """A batch as one int32 device array ``[h | t | neg.reshape(-1) | r]``.

    ``ids`` is the only leaf; ``b`` and ``neg_shape`` are static, so a jitted
    step compiles once per batch shape, as with the dict form.
    """

    ids: jnp.ndarray  # (3b + prod(neg_shape),) int32
    b: int
    neg_shape: Tuple[int, ...]


def dense_step_batch(
    batch: Union[PackedBatch, Dict[str, jnp.ndarray]],
) -> Dict[str, jnp.ndarray]:
    """Lower a global-id batch to the step's workspace form.

    ``batch`` is a ``PackedBatch`` (``batch_to_device``) or a dict of h, r,
    t (b,) and neg; both give the same ids in the same slots.
    """
    if isinstance(batch, PackedBatch):
        b, neg_shape = batch.b, batch.neg_shape
        n_ent = 2 * b + math.prod(neg_shape)
        ent_ids, rel_ids = batch.ids[:n_ent], batch.ids[n_ent:]
    else:
        h, r, t, neg = batch["h"], batch["r"], batch["t"], batch["neg"]
        b, neg_shape = h.shape[0], neg.shape
        ent_ids = jnp.concatenate([h, t, neg.reshape(-1)]).astype(jnp.int32)
        rel_ids = r.astype(jnp.int32)
    n_neg = math.prod(neg_shape)
    return {
        "ent_ids": ent_ids,
        "rel_ids": rel_ids,
        "h_slot": jnp.arange(b, dtype=jnp.int32),
        "t_slot": b + jnp.arange(b, dtype=jnp.int32),
        "neg_slot": 2 * b + jnp.arange(n_neg, dtype=jnp.int32).reshape(neg_shape),
        "rel_slot": jnp.arange(b, dtype=jnp.int32),
    }


def flush_state(cfg: KGEConfig, state: KGEState) -> KGEState:
    """Apply any pending (deferred) entity update — call before eval/save."""
    if state.pend_ids is None:
        return state
    ent = DenseStore(state.entity, state.ent_gsq, state.pend_ids,
                     state.pend_grads, lr=cfg.lr, defer=True).flush()
    return dataclasses.replace(state, entity=ent.table, ent_gsq=ent.gsq,
                               pend_ids=ent.pend_ids, pend_grads=ent.pend_grads)


# --------------------------------------------------------------------------
def train_step(
    cfg: KGEConfig,
    state: KGEState,
    batch: Union[PackedBatch, Dict[str, jnp.ndarray]],
    pairwise_fn=None,
) -> Tuple[KGEState, Dict[str, jnp.ndarray]]:
    """One sparse mini-batch step (jit-able; batch arrays are device arrays).

    batch: a ``PackedBatch``, or a dict of h, r, t (b,) and neg
    (MODES, ng, k) (see ``dense_step_batch``).
    """
    stores, metrics = store_train_step(
        cfg, stores_from_state(cfg, state), dense_step_batch(batch),
        pairwise_fn=pairwise_fn)
    return state_from_stores(state, stores), metrics


def _named(fn, *args, **kw):
    """``functools.partial`` under ``fn``'s name, so the compiled step's
    name stack reads ``jit(<fn name>)`` and not ``jit(<unknown>)``."""
    step = functools.update_wrapper(functools.partial(fn, *args, **kw), fn)
    del step.__wrapped__  # jit reads the partial's own signature (argument names)
    return step


def make_train_step(cfg: KGEConfig, pairwise_fn=None):
    return jax.jit(_named(train_step, cfg, pairwise_fn=pairwise_fn))


# --------------------------------------------------------------------------
# Hogwild two-phase step (paper §3.1, launch/runtime.py): gradients computed
# against a possibly STALE published state, applied to the LATEST one. See
# the staleness/flush contract in embeddings/store.py.
# --------------------------------------------------------------------------
def grad_step(cfg: KGEConfig, state: KGEState, batch, pairwise_fn=None):
    """Phases 2–3 of the step against ``state`` (possibly stale).

    Multi-trainer requires immediate updates (``overlap=False``): Hogwild
    already overlaps update with compute, and a deferred pending buffer is
    single-writer by construction.
    """
    if state.pend_ids is not None:
        raise ValueError("Hogwild trainers require overlap off: "
                         "init_state(..., overlap=False)")
    return store_grads(cfg, stores_from_state(cfg, state),
                       dense_step_batch(batch), pairwise_fn=pairwise_fn)


def apply_step(cfg: KGEConfig, state: KGEState, batch, grads) -> KGEState:
    """Phase 4: apply ``grads`` (from ``grad_step``) to ``state``.

    In the runtime this is dispatched inside ``StoreSlot.swap`` so it always
    lands on the latest published state — no trainer's update is lost.
    """
    stores = store_apply_grads(stores_from_state(cfg, state),
                               dense_step_batch(batch), grads)
    return state_from_stores(state, stores)


def make_hogwild_step(cfg: KGEConfig, pairwise_fn=None):
    """(grad_fn, apply_fn) pair for ``train_loop(..., split_step=...)``."""
    g = jax.jit(_named(grad_step, cfg, pairwise_fn=pairwise_fn))
    a = jax.jit(_named(apply_step, cfg))
    return g, a


def batch_to_device(batch: Union[KGBatch, NaiveBatch]) -> PackedBatch:
    """The host-to-device copy of one batch (span ``pipeline/to_device``):
    one int32 host array, one ``device_put``. A transfer pays a fixed cost
    per call, which a batch of a few thousand ids would pay once per field."""
    with telemetry.span("pipeline/to_device"):
        ids = np.concatenate([batch.h, batch.t, batch.neg.reshape(-1), batch.r],
                             dtype=np.int32)
        out = PackedBatch(jax.device_put(ids), batch.h.shape[0],
                          tuple(batch.neg.shape))
    telemetry.inc("pipeline/to_device_transfers")
    telemetry.inc("pipeline/to_device_bytes", ids.nbytes)
    return out


# --------------------------------------------------------------------------
# Naive baseline step: independent negatives per triplet (paper's strawman).
# Memory/compute O(b*k*d) — used by benchmarks/bench_negative_sampling.py.
# Same stores, same update path; only the negative layout differs.
# --------------------------------------------------------------------------
def naive_train_step(cfg: KGEConfig, state: KGEState, batch):
    if state.pend_ids is not None:
        raise ValueError("naive_train_step does not support overlap (T5) "
                         "state; init_state(..., overlap=False)")
    stores, metrics = store_train_step(
        cfg, stores_from_state(cfg, state), dense_step_batch(batch),
        neg_mode="naive")
    return state_from_stores(state, stores), metrics
