"""The one KGE train step, parameterized by EmbeddingStores.

Every trainer in the repo — single-machine joint/naive, the Hogwild
multi-trainer runtime, and the shard_map cluster path — is this function
applied to different store backends:

    single machine   stores = DenseStore(entity/rel[/proj])
    distributed      stores = ShardedStore(entity/rel[/proj]) +
                              ReplicatedStore(shared split relations),
                     called per-device inside compat.shard_map

The step follows the paper's update discipline (§2, §3.4, T5):

  1. ``flush()`` the entity store — applies the previous step's deferred
     gradients (overlap on) or is a no-op (overlap off);
  2. ``gather()`` the workspace rows (post-update — see core/distributed.py
     for why we read fresh rows rather than literal paper staleness);
  3. score + loss + grads w.r.t. the *workspace rows only* (sparse);
  4. ``apply_sparse_grads()`` on every touched table — the stores decide
     whether to apply now or defer, and where rows physically live.

Phases 2–3 and phase 4 are also exposed separately (``store_grads`` /
``store_apply_grads``) for the Hogwild multi-trainer runtime (paper §3.1,
launch/runtime.py): a trainer computes ``store_grads`` against a possibly
*stale* published store and applies them with ``store_apply_grads`` to the
*latest* one — the staleness/flush contract is documented in
embeddings/store.py. ``store_train_step`` is exactly the composition of the
two phases on the same (flushed) store.

Batch normal form (what both samplers lower to):

    ent_ids   store-address of the entity workspace (array / ShardedIds)
    rel_ids   store-address of the relation workspace
    h_slot, t_slot   (b,)  workspace slots of heads / tails
    neg_slot  (MODES, ng, k) joint  |  (MODES, b, k) naive — workspace slots
    rel_slot  (b,)  relation-workspace slots
    rel_shared (b,) optional: row in the shared relation table, -1 = owned
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import KGEConfig
from repro.core import losses as L
from repro.core import scores as S
from repro.core.sampling import MODES
from repro.embeddings.table import emb_init_scale
from repro.optim.sparse_adagrad import add_rows

Stores = Dict[str, object]  # "entity", "rel", optional "proj", "shared"


@jax.named_scope("kge.score_grad")
def store_grads(
    cfg: KGEConfig,
    stores: Stores,
    batch: Dict[str, jnp.ndarray],
    *,
    neg_mode: str = "joint",
    ctx: Optional[S.ShardCtx] = None,
    n_servers: int = 1,
    pairwise_fn=None,
    prefetched: Optional[Dict[str, jnp.ndarray]] = None,
) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
    """Phases 2–3: gather workspaces + loss/metrics + sparse row gradients.

    Returns ``({store name: workspace-row grads}, metrics)``. Does NOT
    ``flush()`` — a Hogwild trainer gathers from the published store as-is
    (stale reads tolerated, paper §3.1); the one-shot ``store_train_step``
    flushes before calling this.

    ``prefetched`` (the pipelined path) supplies the entity/relation
    workspaces already pulled during the previous step — the gathers are
    skipped and gradients are computed against those one-step-stale rows
    (the depth-1 staleness contract, ``prefetch_workspaces``).

    Its device ops carry the scope ``kge.score_grad``.
    """
    ctx = S.ShardCtx(None) if ctx is None else ctx
    scale = emb_init_scale(cfg)
    h_slot, t_slot = batch["h_slot"], batch["t_slot"]
    rel_slot, neg_slot = batch["rel_slot"], batch["neg_slot"]
    rel_shared = batch.get("rel_shared")
    has_shared = "shared" in stores and rel_shared is not None
    has_proj = "proj" in stores

    # ---- 2. pull the workspaces (or reuse the previous step's prefetch)
    ent = stores["entity"]
    rel_store = stores["rel"]
    if prefetched is not None:
        ws, rel_ws = prefetched["entity"], prefetched["rel"]
    else:
        ws = ent.gather(batch["ent_ids"])
        rel_ws = rel_store.gather(batch["rel_ids"])
    proj_ws = stores["proj"].gather(batch["rel_ids"]) if has_proj else None
    # each triplet's projection row; the loss is differentiated w.r.t. these
    # and ``add_rows`` sums their gradients onto the workspace rows (the
    # transpose of this gather would be one scatter of 40,000-wide rows,
    # which XLA runs serially on a TPU)
    proj_rows = proj_ws[rel_slot] if has_proj else None
    shared_rows = stores["shared"].gather(rel_shared) if has_shared else None
    is_shared = (rel_shared >= 0)[:, None] if has_shared else None

    b = h_slot.shape[0]
    k = cfg.neg_sample_size
    ng = neg_slot.shape[1]  # (MODES, ng, k) joint, (MODES, b, k) naive
    # negative-sharding: local (b, k/S) score slices + scalar loss psum,
    # instead of psum-ing (b, k) scores.
    sharded_negs = (
        neg_mode == "joint"
        and ctx.axis is not None
        and cfg.model not in ("transr", "rescal")
        and cfg.loss in ("logistic", "ranking")
        and k % n_servers == 0
    )

    # ---- 3. loss + grads w.r.t. workspace rows ONLY (sparse, paper §2)
    def loss_fn(ws_, rel_ws_, shared_rows_, pr):
        h, t = ws_[h_slot], ws_[t_slot]
        r = rel_ws_[rel_slot]
        if is_shared is not None:
            r = jnp.where(is_shared, shared_rows_, r)
        pos = S.positive_score(cfg.model, h, r, t, cfg.gamma, ctx,
                               r_proj=pr, rel_dim=cfg.rel_dim, emb_scale=scale)

        # joint negatives (T1): one pool of k entities per group of gsz
        # triplets; naive negatives are the same with one triplet a group
        gsz = b // ng
        neg_out = []
        for m in range(MODES):
            corrupt = "tail" if m == 0 else "head"
            e = (h if m == 0 else t).reshape(ng, gsz, -1)
            rg = r.reshape(ng, gsz, -1)
            prg = None if pr is None else pr.reshape(ng, gsz, -1)
            negs = ws_[neg_slot[m]]  # (ng, k, d)
            if cfg.model == "transr":
                # the group axis goes whole to projected_l2sq (the kernel's
                # grid on a TPU), not through vmap
                neg_out.append(S.negative_score(
                    cfg.model, e, rg, negs, corrupt, cfg.gamma, ctx,
                    r_proj=prg, rel_dim=cfg.rel_dim))
            elif sharded_negs:
                f = jax.vmap(lambda e1, r1, n1: S.negative_score_sharded(
                    cfg.model, e1, r1, n1, corrupt, cfg.gamma, ctx,
                    emb_scale=scale, pairwise_fn=pairwise_fn,
                    wire_dtype=cfg.comm_dtype))
                neg_out.append(f(e, rg, negs))  # (ng, gsz, k/S) local
            else:
                f = jax.vmap(lambda e1, r1, n1, p1=prg: S.negative_score(
                    cfg.model, e1, r1, n1, corrupt, cfg.gamma, ctx,
                    r_proj=None if prg is None else p1, rel_dim=cfg.rel_dim,
                    emb_scale=scale, pairwise_fn=pairwise_fn),
                    in_axes=(0, 0, 0) if prg is None else (0, 0, 0, 0))
                neg_out.append(f(e, rg, negs) if prg is None
                               else f(e, rg, negs, prg))
        neg = jnp.stack(neg_out)  # (MODES, ng, gsz, k or k/S)
        if sharded_negs:
            # scalar-reduced loss: identical value on every server
            posf = jnp.concatenate([pos, pos])
            if cfg.loss == "logistic":
                neg_sum = jax.lax.psum(jnp.sum(jax.nn.softplus(neg)), ctx.axis)
                loss = (jnp.mean(jax.nn.softplus(-posf))
                        + neg_sum / (MODES * b * k))
            else:  # ranking: pair each positive with its group's negatives
                p2 = jnp.stack([pos, pos]).reshape(MODES, ng, gsz, 1)
                h_ = jnp.maximum(0.0, cfg.gamma - p2 + neg)
                loss = jax.lax.psum(jnp.sum(h_), ctx.axis) / (MODES * b * k)
            neg_mean = jax.lax.psum(jnp.sum(neg), ctx.axis) / (MODES * b * k)
            return loss, (jnp.mean(pos), neg_mean)
        loss = L.kge_loss(cfg.loss, jnp.concatenate([pos, pos]),
                          neg.reshape(MODES * b, -1), margin=cfg.gamma)
        return loss, (jnp.mean(pos), jnp.mean(neg))

    argnums = [0, 1] + ([2] if has_shared else []) + ([3] if has_proj else [])
    (loss, (pos_m, neg_m)), grads = jax.value_and_grad(
        loss_fn, argnums=tuple(argnums), has_aux=True
    )(ws, rel_ws, shared_rows, proj_rows)
    gmap = dict(zip(argnums, grads))

    out = {"entity": gmap[0], "rel": gmap[1]}
    if has_shared:
        out["shared"] = gmap[2]
    if has_proj:
        out["proj"] = add_rows(proj_ws.shape[0], rel_slot, gmap[3]).astype(
            proj_ws.dtype)
    metrics = {"loss": loss, "pos_score": pos_m, "neg_score": neg_m}
    return out, metrics


def store_apply_grads(
    stores: Stores,
    batch: Dict[str, jnp.ndarray],
    grads: Dict[str, jnp.ndarray],
) -> Stores:
    """Phase 4: every row update goes through EmbeddingStore.apply_sparse_grads.

    In Hogwild mode this runs inside ``StoreSlot.swap`` against the *latest*
    published stores, which may be newer than the ones ``store_grads`` read —
    no update is ever lost, only computed against slightly stale rows.
    """
    new_stores = dict(stores)
    new_stores["entity"] = stores["entity"].apply_sparse_grads(
        batch["ent_ids"], grads["entity"])
    new_stores["rel"] = stores["rel"].apply_sparse_grads(
        batch["rel_ids"], grads["rel"])
    if "shared" in grads:
        new_stores["shared"] = stores["shared"].apply_sparse_grads(
            batch["rel_shared"], grads["shared"])
    if "proj" in grads:
        new_stores["proj"] = stores["proj"].apply_sparse_grads(
            batch["rel_ids"], grads["proj"])
    return new_stores


def store_train_step(
    cfg: KGEConfig,
    stores: Stores,
    batch: Dict[str, jnp.ndarray],
    *,
    neg_mode: str = "joint",
    ctx: Optional[S.ShardCtx] = None,
    n_servers: int = 1,
    machine_axis=None,
    pairwise_fn=None,
) -> Tuple[Stores, Dict[str, jnp.ndarray]]:
    """One sparse mini-batch step over pluggable stores (jit/shard_map-able).

    The composition flush → ``store_grads`` → ``store_apply_grads`` on one
    store set (grads applied to the stores they were computed against).

    The phases are named scopes on the device ops: ``kge.flush``,
    ``kge.score_grad`` (in ``store_grads``) and ``kge.apply``; inside the
    first and last, every sparse update splits into ``kge.adagrad_dedup``
    and ``kge.adagrad_update`` (optim/sparse_adagrad.py).

    When the entity store defers (T5), ``metrics["pend_dropped"]`` reports
    the store's capacity-bounded defer drop count — updates silently lost
    under pend-buffer pressure become a visible metric (and a warn-once log
    in ``launch/engine.LoggingHook``).
    """
    # ---- 1. flush deferred updates (T5) before gathering
    stores = dict(stores)
    with jax.named_scope("kge.flush"):
        stores["entity"] = stores["entity"].flush()
    grads, metrics = store_grads(
        cfg, stores, batch, neg_mode=neg_mode, ctx=ctx,
        n_servers=n_servers, pairwise_fn=pairwise_fn)
    with jax.named_scope("kge.apply"):
        new_stores = store_apply_grads(stores, batch, grads)
    ent = new_stores["entity"]
    if getattr(ent, "defer", False) and getattr(ent, "pend_dropped", None) is not None:
        metrics = dict(metrics,
                       pend_dropped=ent.pend_dropped.astype(jnp.float32))
    if getattr(ent, "coalesce", False):
        metrics = dict(metrics,
                       push_dropped=ent.co_dropped.astype(jnp.float32))
    if machine_axis is not None:
        metrics = {name: jax.lax.pmean(v, machine_axis)
                   for name, v in metrics.items()}
    return new_stores, metrics


def prefetch_workspaces(stores: Stores, batch: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Issue the entity/relation workspace pulls for the NEXT batch.

    The depth-1 staleness contract (``--pipeline-depth 1``): the pull reads
    the *current* tables, before this step's gradients apply, so the rows
    the next step computes against are at most one update stale — exactly a
    Hogwild stale read (embeddings/store.py), and the gradients still apply
    to the latest table. Issued in program order BEFORE the push/apply so
    async dispatch overlaps the pull collectives with the update.
    """
    ent, rel = stores["entity"], stores["rel"]
    return {
        "entity": (ent.gather_prefetch(batch["ent_ids"])
                   if hasattr(ent, "gather_prefetch")
                   else ent.gather(batch["ent_ids"])),
        "rel": (rel.gather_prefetch(batch["rel_ids"])
                if hasattr(rel, "gather_prefetch")
                else rel.gather(batch["rel_ids"])),
    }


def store_pipelined_step(
    cfg: KGEConfig,
    stores: Stores,
    batch: Dict[str, jnp.ndarray],
    prefetched: Dict[str, jnp.ndarray],
    next_batch: Dict[str, jnp.ndarray],
    *,
    ctx: Optional[S.ShardCtx] = None,
    n_servers: int = 1,
    machine_axis=None,
    pairwise_fn=None,
) -> Tuple[Stores, Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
    """Depth-1 pipelined ``store_train_step``: grads from the PREVIOUS
    step's prefetched workspaces, pull for the next batch issued before the
    push/apply of this one.

    Returns ``(stores, next_prefetched, metrics)``. No flush phase: the
    pipelined path requires T5 defer off (the pipeline already provides the
    overlap, and both contracts are single-writer — enforced by
    ``core.distributed.make_program``). ``next_batch`` only needs the
    ``ent_ids``/``rel_ids`` addresses. Scopes: ``kge.score_grad``,
    ``kge.prefetch``, ``kge.apply``.
    """
    grads, metrics = store_grads(
        cfg, stores, batch, ctx=ctx, n_servers=n_servers,
        pairwise_fn=pairwise_fn, prefetched=prefetched)
    with jax.named_scope("kge.prefetch"):
        new_pf = prefetch_workspaces(stores, next_batch)
    with jax.named_scope("kge.apply"):
        new_stores = store_apply_grads(stores, batch, grads)
    ent = new_stores["entity"]
    if getattr(ent, "coalesce", False):
        metrics = dict(metrics,
                       push_dropped=ent.co_dropped.astype(jnp.float32))
    if machine_axis is not None:
        metrics = {name: jax.lax.pmean(v, machine_axis)
                   for name, v in metrics.items()}
    return new_stores, new_pf, metrics
