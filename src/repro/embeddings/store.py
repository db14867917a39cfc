"""Pluggable embedding stores — the single update surface of the trainer.

DGL-KE's core architectural claim is that one embedding-access abstraction
(sparse Adagrad row updates behind a KVStore) serves every deployment from a
single many-core machine to a cluster. This module is that abstraction for
the JAX reproduction: every train step gathers rows and applies sparse
gradients through an ``EmbeddingStore`` and never touches tables directly.

Three backends:

* ``DenseStore``    — one whole table on the local device(s); the
  single-machine path (paper's many-core trainer). Supports the T5 deferred
  ("overlapped") update via its pending buffers, so overlap is no longer a
  distributed-only feature.
* ``ShardedStore``  — a machine-local block of a row-partitioned table plus
  the KVStore pull/push collectives (embeddings/kvstore.py). Runs inside
  ``compat.shard_map``; with ``machine_axis=None`` (n_parts == 1) the
  collectives degrade to local gathers and the store runs anywhere — that
  degenerate mode is what the single↔distributed parity tests exercise.
* ``ReplicatedStore`` — a small table replicated over machines (the "shared"
  split relations of T4), updated by scatter + psum.

All stores are functional pytrees: ``apply_sparse_grads``/``flush`` return a
new store. The persistence surface is ``snapshot()`` (a flat dict of arrays,
checkpointable with common/checkpoint.py) and ``restore(snapshot)``.

Update semantics shared by all backends (paper §3.4 + T5):

    store = store.flush()                      # apply last step's deferred grads
    rows  = store.gather(ids)                  # read post-update rows
    ...compute grads w.r.t. rows...
    store = store.apply_sparse_grads(ids, g)   # apply now, or defer if overlap

Hogwild multi-trainer contract (paper §3.1, launch/runtime.py):

* ``gather`` may legally read a *stale* published store: a trainer computes
  gradients against whatever version ``StoreSlot.read()`` returned while
  other trainers keep publishing. Sparse Adagrad tolerates this exactly as
  the paper's lock-free shared-memory updates do.
* ``apply_sparse_grads`` must land on the *latest* published store (inside
  ``StoreSlot.swap``) — staleness only affects which rows gradients were
  computed against, never which updates survive; no trainer's update is
  overwritten. Stores stay functional pytrees, so every published store is
  an internally consistent snapshot (checkpoint/eval hooks never see a torn
  state).
* ``defer=True`` (T5) and multi-trainer are mutually exclusive: the pending
  buffers are single-writer by design, and Hogwild already overlaps the
  update with compute. Flush therefore only happens at barriers — before
  eval/checkpoint and at loop end, when no trainer holds an unapplied
  gradient (``core/step.py`` flushes inside the one-shot step; the runtime's
  hooks receive already-published states and flush via their ``flush_fn``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.common import telemetry
from repro.embeddings.kvstore import (
    KVStoreSpec,
    pull_local,
    pull_remote,
    push_remote_grads,
)
from repro.optim.sparse_adagrad import (
    AdagradState,
    dedup_compact_rows,
    dense_adagrad_update,
    sparse_adagrad_apply,
)

Snapshot = Dict[str, jnp.ndarray]


@runtime_checkable
class EmbeddingStore(Protocol):
    """What a train step may do with an embedding table."""

    def gather(self, ids) -> jnp.ndarray: ...

    def apply_sparse_grads(self, ids, grads) -> "EmbeddingStore": ...

    def flush(self) -> "EmbeddingStore": ...

    def snapshot(self) -> Snapshot: ...

    def restore(self, snap: Snapshot) -> "EmbeddingStore": ...


def _empty_pending(width: int, slots: int = 0, dtype=jnp.float32):
    return (jnp.full((slots,), -1, jnp.int32), jnp.zeros((slots, width), dtype))


def _adagrad_rows(table, gsq, ids, grads, lr):
    """Aggregate duplicate ids, then sparse-Adagrad the touched rows.

    Delegates to ``optim.sparse_adagrad_apply``, which dispatches between the
    jnp path and the fused Pallas kernel per its platform-default
    ``use_kernel`` flag — stores and trainers never choose a path themselves.
    """
    return sparse_adagrad_apply(table, gsq, ids, grads, lr)


def _park_pending(pend_ids, pend_grads, ids, grads):
    """Stage one step's grads into the fixed pend buffer (T5 defer).

    When the buffer matches the raw workspace size, parking is a passthrough
    (the flush dedups anyway). A *smaller* buffer triggers the
    capacity-bounded dedup-before-defer: duplicates are aggregated and the
    unique rows compacted into ``pend_slots``, so deferred memory is bounded
    by the expected unique count rather than the workspace size.

    Returns ``(ids, grads, n_dropped)``: uniques beyond capacity are dropped
    (their updates are LOST) — callers accumulate ``n_dropped`` into the
    store's ``pend_dropped`` so the loss is observable, not silent (it
    surfaces as the ``pend_dropped`` step metric and a warn-once log; see
    launch/engine.py and docs/TELEMETRY.md).
    """
    cap = pend_ids.shape[0]
    if cap == ids.shape[0]:
        return (ids.astype(jnp.int32), grads.astype(pend_grads.dtype),
                jnp.zeros((), jnp.int32))
    out_ids, out_grads, n_dropped = dedup_compact_rows(ids, grads, cap)
    return out_ids, out_grads.astype(pend_grads.dtype), n_dropped


def _coalesce_remote(co_ids, co_grads, req, g_remote):
    """Merge one step's remote grads into the per-peer coalesce buffers.

    ``_park_pending`` applied per peer: for each peer ``p`` the already-
    buffered ``(co_ids[p], co_grads[p])`` and this step's ``(req[p],
    g_remote[p])`` are dedup-aggregated and compacted back into the fixed
    per-peer capacity by ``dedup_compact_rows``. Uniques beyond capacity are
    dropped (counted — surfaced as the ``push_dropped`` step metric). The
    jnp dedup path is forced: the merge runs under ``vmap`` over peers,
    where the Pallas dedup kernel's scalar-prefetch layout does not apply.

    Returns ``(ids (P, Ck), grads (P, Ck, d), n_dropped scalar)``.
    """
    def merge(ci, cg, ri, rg):
        ids = jnp.concatenate([ci, ri.astype(jnp.int32)])
        g = jnp.concatenate([cg, rg.astype(cg.dtype)], axis=0)
        return dedup_compact_rows(ids, g, ci.shape[0], use_kernel=False)

    ids, grads, dropped = jax.vmap(merge)(co_ids, co_grads, req, g_remote)
    return ids, grads, jnp.sum(dropped)


# ===========================================================================
@dataclasses.dataclass
class DenseStore:
    """Whole-table store (single-machine path). ``ids`` are global rows.

    ``defer=True`` holds each step's aggregate gradient in the pending
    buffers and applies it at the *next* step's ``flush()`` — the paper's T5
    overlap, previously exclusive to the distributed path.
    """

    table: jnp.ndarray  # (n_rows, d)
    gsq: jnp.ndarray  # Adagrad accumulator, same shape
    pend_ids: jnp.ndarray  # (Lp,) int32, -1 pad; (0,) when defer off
    pend_grads: jnp.ndarray  # (Lp, d)
    lr: float = 0.1  # static
    defer: bool = False  # static
    # uniques dropped by the capacity-bounded defer over this store's
    # lifetime (adapters rebuild stores each step, so there it reads as the
    # per-step drop count) — surfaced as the ``pend_dropped`` step metric
    pend_dropped: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.zeros((), jnp.int32))

    @classmethod
    def create(cls, table: jnp.ndarray, lr: float, defer: bool = False,
               pend_slots: int = 0) -> "DenseStore":
        pid, pg = _empty_pending(table.shape[-1], pend_slots if defer else 0,
                                 table.dtype)
        return cls(table=table, gsq=jnp.zeros_like(table), pend_ids=pid,
                   pend_grads=pg, lr=lr, defer=defer)

    def gather(self, ids: jnp.ndarray) -> jnp.ndarray:
        return self.table[ids]

    def apply_sparse_grads(self, ids, grads) -> "DenseStore":
        if self.defer:
            # T5: park this step's grads; flush() applies them next step
            pid, pg, nd = _park_pending(self.pend_ids, self.pend_grads,
                                        ids, grads)
            return dataclasses.replace(self, pend_ids=pid, pend_grads=pg,
                                       pend_dropped=self.pend_dropped + nd)
        table, gsq = _adagrad_rows(self.table, self.gsq, ids, grads, self.lr)
        return dataclasses.replace(self, table=table, gsq=gsq)

    def flush(self) -> "DenseStore":
        if self.pend_ids.shape[0] == 0:
            return self
        telemetry.inc("store/flush_calls")
        table, gsq = _adagrad_rows(self.table, self.gsq, self.pend_ids,
                                   self.pend_grads, self.lr)
        pid, pg = (jnp.full_like(self.pend_ids, -1),
                   jnp.zeros_like(self.pend_grads))
        return dataclasses.replace(self, table=table, gsq=gsq,
                                   pend_ids=pid, pend_grads=pg)

    def snapshot(self) -> Snapshot:
        return {"table": self.table, "gsq": self.gsq,
                "pend_ids": self.pend_ids, "pend_grads": self.pend_grads}

    def restore(self, snap: Snapshot) -> "DenseStore":
        return dataclasses.replace(self, **snap)


jax.tree_util.register_dataclass(
    DenseStore,
    data_fields=["table", "gsq", "pend_ids", "pend_grads", "pend_dropped"],
    meta_fields=["lr", "defer"],
)


# ===========================================================================
class ShardedIds(NamedTuple):
    """Addresses for one machine's pull: block-local rows + per-peer requests."""

    local: jnp.ndarray  # (L,) machine-local row ids, -1 pad
    remote: jnp.ndarray  # (n_parts, Rp) peer-local row ids, -1 pad


@dataclasses.dataclass
class ShardedStore:
    """Partition-local block of a row-sharded table + KVStore collectives.

    Inside ``compat.shard_map`` the collectives run over ``spec.machine_axis``;
    with ``machine_axis=None`` (the n_parts == 1 degenerate KVStore) remote
    requests are served from the local block and the store needs no mesh.
    """

    table: jnp.ndarray  # (rows_local, d or d_shard)
    gsq: jnp.ndarray
    pend_ids: jnp.ndarray  # (Lp,) -1 pad; (0,) when defer off
    pend_grads: jnp.ndarray  # (Lp, d_shard)
    spec: KVStoreSpec = KVStoreSpec(None, 1, 1)  # static
    lr: float = 0.1  # static
    defer: bool = False  # static
    # lifetime drop count of the capacity-bounded defer (see DenseStore)
    pend_dropped: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.zeros((), jnp.int32))
    # micro-batched coalesced push (--push-every K): remote grads accumulate
    # per peer in (n_parts, Ck[, d]) merge buffers across steps and leave in
    # one deduplicated all_to_all at push_flush(); (n_parts, 0[, d]) when off
    co_ids: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.zeros((1, 0), jnp.int32))
    co_grads: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.zeros((1, 0, 1), jnp.float32))
    # per-step drop count of the capacity-bounded coalesce buffers
    co_dropped: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.zeros((), jnp.int32))
    coalesce: bool = False  # static

    def __post_init__(self):
        if self.coalesce and self.defer:
            raise ValueError(
                "coalesce and defer are mutually exclusive: both hold this "
                "step's grads back, and mixing their buffers would apply "
                "remote rows on a different cadence than local ones")

    @classmethod
    def create(cls, table: jnp.ndarray, spec: KVStoreSpec, lr: float,
               defer: bool = False, pend_slots: int = 0,
               coalesce_slots: int = 0) -> "ShardedStore":
        pid, pg = _empty_pending(table.shape[-1], pend_slots if defer else 0,
                                 table.dtype)
        co_i = jnp.full((spec.n_parts, coalesce_slots), -1, jnp.int32)
        co_g = jnp.zeros((spec.n_parts, coalesce_slots, table.shape[-1]),
                         table.dtype)
        return cls(table=table, gsq=jnp.zeros_like(table), pend_ids=pid,
                   pend_grads=pg, spec=spec, lr=lr, defer=defer,
                   co_ids=co_i, co_grads=co_g, coalesce=coalesce_slots > 0)

    def gather(self, ids: ShardedIds) -> jnp.ndarray:
        """Workspace = [local rows (L,); remote rows (n_parts * Rp,)]."""
        loc = pull_local(self.table, ids.local)
        rem = pull_remote(self.table, ids.remote, self.spec)
        return jnp.concatenate([loc, rem], axis=0)

    def gather_prefetch(self, ids: ShardedIds) -> jnp.ndarray:
        """``gather`` for the pipelined one-step lookahead (same rows, same
        collectives) — the remote pull is accounted as ``kvstore/prefetch_*``
        so eager and prefetched ICI traffic stay separable."""
        loc = pull_local(self.table, ids.local)
        rem = pull_remote(self.table, ids.remote, self.spec,
                          metric_prefix="kvstore/prefetch")
        return jnp.concatenate([loc, rem], axis=0)

    def apply_sparse_grads(self, ids: ShardedIds, grads) -> "ShardedStore":
        """``grads`` covers the whole workspace returned by ``gather``."""
        L = ids.local.shape[0]
        g_local, g_remote = grads[:L], grads[L:]
        if self.coalesce:
            # local rows update now; remote grads merge into the per-peer
            # coalesce buffers and leave at the next push_flush()
            n_parts = ids.remote.shape[0]
            ci, cg, nd = _coalesce_remote(
                self.co_ids, self.co_grads, ids.remote,
                g_remote.reshape(n_parts, -1, g_remote.shape[-1]))
            table, gsq = _adagrad_rows(self.table, self.gsq, ids.local,
                                       g_local, self.lr)
            return dataclasses.replace(self, table=table, gsq=gsq,
                                       co_ids=ci, co_grads=cg,
                                       co_dropped=self.co_dropped + nd)
        owner_ids, owner_grads = push_remote_grads(g_remote, ids.remote, self.spec)
        all_ids = jnp.concatenate([ids.local, owner_ids]).astype(jnp.int32)
        all_grads = jnp.concatenate([g_local, owner_grads], axis=0)
        if self.defer:
            pid, pg, nd = _park_pending(self.pend_ids, self.pend_grads,
                                        all_ids, all_grads)
            return dataclasses.replace(self, pend_ids=pid, pend_grads=pg,
                                       pend_dropped=self.pend_dropped + nd)
        table, gsq = _adagrad_rows(self.table, self.gsq, all_ids, all_grads,
                                   self.lr)
        return dataclasses.replace(self, table=table, gsq=gsq)

    def push_flush(self) -> "ShardedStore":
        """Flush the coalesce buffers: ONE deduplicated all_to_all returns
        the accumulated remote grads to their owners, owners apply them with
        sparse Adagrad, and the buffers reset. No-op when coalescing is off.

        Numerics: the merge already summed duplicate rows, so one flush of K
        steps' grads equals applying their per-row sums in a single Adagrad
        step — the flush-equivalence the coalesce tests assert.
        """
        if not self.coalesce:
            return self
        n_parts, ck = self.co_ids.shape
        owner_ids, owner_grads = push_remote_grads(
            self.co_grads.reshape(n_parts * ck, -1), self.co_ids, self.spec,
            metric_prefix="kvstore/coalesced_push")
        table, gsq = _adagrad_rows(self.table, self.gsq, owner_ids,
                                   owner_grads, self.lr)
        return dataclasses.replace(
            self, table=table, gsq=gsq,
            co_ids=jnp.full_like(self.co_ids, -1),
            co_grads=jnp.zeros_like(self.co_grads))

    def flush(self) -> "ShardedStore":
        if self.pend_ids.shape[0] == 0:
            return self
        telemetry.inc("store/flush_calls")
        table, gsq = _adagrad_rows(self.table, self.gsq, self.pend_ids,
                                   self.pend_grads, self.lr)
        pid, pg = (jnp.full_like(self.pend_ids, -1),
                   jnp.zeros_like(self.pend_grads))
        return dataclasses.replace(self, table=table, gsq=gsq,
                                   pend_ids=pid, pend_grads=pg)

    def snapshot(self) -> Snapshot:
        snap = {"table": self.table, "gsq": self.gsq,
                "pend_ids": self.pend_ids, "pend_grads": self.pend_grads}
        if self.coalesce:
            snap["co_ids"] = self.co_ids
            snap["co_grads"] = self.co_grads
        return snap

    def restore(self, snap: Snapshot) -> "ShardedStore":
        return dataclasses.replace(self, **snap)


jax.tree_util.register_dataclass(
    ShardedStore,
    data_fields=["table", "gsq", "pend_ids", "pend_grads", "pend_dropped",
                 "co_ids", "co_grads", "co_dropped"],
    meta_fields=["spec", "lr", "defer", "coalesce"],
)


# ===========================================================================
@dataclasses.dataclass
class ReplicatedStore:
    """Small machine-replicated table (T4 "shared" split relations).

    Gradients are scattered into a full-table buffer and psum'd over the
    machine axis, so every replica applies the identical Adagrad step.
    """

    table: jnp.ndarray  # (n_rows, d)
    gsq: jnp.ndarray
    lr: float = 0.1  # static
    machine_axis: object = None  # static: None | str | tuple of str
    eps: float = 1e-10  # static

    @classmethod
    def create(cls, table: jnp.ndarray, lr: float,
               machine_axis=None) -> "ReplicatedStore":
        return cls(table=table, gsq=jnp.zeros_like(table), lr=lr,
                   machine_axis=machine_axis)

    def gather(self, ids: jnp.ndarray) -> jnp.ndarray:
        """Rows for ids; -1 pads return row 0 (callers mask)."""
        return self.table[jnp.maximum(ids, 0)]

    def apply_sparse_grads(self, ids, grads) -> "ReplicatedStore":
        flat_ids = ids.reshape(-1).astype(jnp.int32)
        flat_grads = grads.reshape(flat_ids.shape[0], -1)
        if self.machine_axis is None:
            # local replica: the sparse path (untouched rows are exact
            # no-ops, so numerics equal the dense scatter formulation)
            table, gsq = sparse_adagrad_apply(
                self.table, self.gsq, flat_ids, flat_grads, self.lr, self.eps)
            return dataclasses.replace(self, table=table, gsq=gsq)
        # cross-machine: the psum needs the dense full-table gradient
        mask = (flat_ids >= 0)[:, None]
        g = jnp.zeros_like(self.table).at[jnp.maximum(flat_ids, 0)].add(
            jnp.where(mask, flat_grads, 0.0))
        g = jax.lax.psum(g, self.machine_axis)
        table, st = dense_adagrad_update(
            self.table, AdagradState(self.gsq), g, self.lr, self.eps)
        return dataclasses.replace(self, table=table, gsq=st.gsq)

    def flush(self) -> "ReplicatedStore":
        return self

    def snapshot(self) -> Snapshot:
        return {"table": self.table, "gsq": self.gsq}

    def restore(self, snap: Snapshot) -> "ReplicatedStore":
        return dataclasses.replace(self, **snap)


jax.tree_util.register_dataclass(
    ReplicatedStore,
    data_fields=["table", "gsq"],
    meta_fields=["lr", "machine_axis", "eps"],
)
