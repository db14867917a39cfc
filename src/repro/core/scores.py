"""KGE score functions (paper Table 1), written dim-shard aware.

Every function takes embeddings that may hold only a ``d/S`` slice of the
true dimension (dim-striping over the 'model' mesh axis — the KVStore-server
axis). Reductions over the embedding dimension go through ``ShardCtx.psum``;
with ``axis=None`` they degrade to plain sums for single-device use, so the
same code serves smoke tests, CPU training, and the 512-chip dry-run.

Layout conventions
------------------
* ComplEx / RotatE use an **interleaved (re, im) pair layout** along dim, so
  any even-sized dim slice holds whole complex numbers and dim-striping is
  sound (see embeddings/table.py).
* TransR / RESCAL store the per-relation projection flattened row-major
  (d, rel_dim) → (d * rel_dim,), dim-striped on the *first* (d) axis: server
  ``s`` holds rows ``M_r[s*ds:(s+1)*ds, :]``, so ``h_s @ M_r_s`` is a partial
  product completed by one psum.

Joint-negative decomposition (paper §3.3, T1)
---------------------------------------------
Every model exposes ``neg_o(...)`` producing the per-triplet vector ``o``
such that the b×k negative scores reduce to a *pairwise* form
``pairwise(o, negs)`` — a GEMM (`dot`, `l2sq`) or an L1 distance — which is
what the Pallas ``kge_score`` kernel implements on the MXU.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.common import compat

AxisName = Union[str, Tuple[str, ...], None]

MODELS = ("transe_l1", "transe_l2", "distmult", "complex", "rotate", "transr", "rescal")
# pairwise reduction used by each model's joint-negative form
PAIRWISE_OF = {
    "transe_l1": "l1",
    "transe_l2": "l2sq",
    "distmult": "dot",
    "complex": "dot",
    "rotate": "l2sq",
    "transr": "l2sq",
    "rescal": "dot",
}
# translational models report gamma - distance
TRANSLATIONAL = {"transe_l1", "transe_l2", "rotate", "transr"}


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Dim-sharding context: which mesh axis stripes the embedding dim."""

    axis: AxisName = None

    def psum(self, x):
        if self.axis is None:
            return x
        return jax.lax.psum(x, self.axis)

    @property
    def size(self) -> int:
        if self.axis is None:
            return 1
        if isinstance(self.axis, tuple):
            import numpy as np

            return int(np.prod([compat.axis_size(a) for a in self.axis]))
        return compat.axis_size(self.axis)

    def index(self):
        if self.axis is None:
            return 0
        return jax.lax.axis_index(self.axis)


def _cmul(a_re, a_im, b_re, b_im):
    return a_re * b_re - a_im * b_im, a_re * b_im + a_im * b_re


def split_ri(x: jnp.ndarray):
    """Interleaved (re, im) pairs -> (re, im), each (..., d/2)."""
    r = x.reshape(x.shape[:-1] + (-1, 2))
    return r[..., 0], r[..., 1]


def merge_ri(re: jnp.ndarray, im: jnp.ndarray):
    return jnp.stack([re, im], axis=-1).reshape(re.shape[:-1] + (-1,))


def _phase(r: jnp.ndarray, scale: float):
    """RotatE: relation slice -> unit-modulus complex (interleaved layout).

    The raw relation row stores phases; only the first half of the slice is
    meaningful (rel dim = d/2 phases for a d-dim entity embedding). We read
    phases from the even positions of the interleaved layout.
    """
    ph = r.reshape(r.shape[:-1] + (-1, 2))[..., 0] / scale * jnp.pi
    return jnp.cos(ph), jnp.sin(ph)


# --------------------------------------------------------------------------
# Positive scores: one per triplet, elementwise + dim reduction
# --------------------------------------------------------------------------
def positive_score(
    model: str,
    h: jnp.ndarray,  # (b, ds)
    r: jnp.ndarray,  # (b, rel_ds)   (phases / complex / plain, per model)
    t: jnp.ndarray,  # (b, ds)
    gamma: float,
    ctx: ShardCtx,
    r_proj: Optional[jnp.ndarray] = None,  # (b, ds * rel_dim_full) TransR/RESCAL
    rel_dim: int = 0,
    emb_scale: float = 1.0,
) -> jnp.ndarray:
    if model == "transe_l1":
        d = ctx.psum(jnp.sum(jnp.abs(h + r - t), axis=-1))
        return gamma - d
    if model == "transe_l2":
        d2 = ctx.psum(jnp.sum(jnp.square(h + r - t), axis=-1))
        return gamma - jnp.sqrt(d2 + 1e-12)
    if model == "distmult":
        return ctx.psum(jnp.sum(h * r * t, axis=-1))
    if model == "complex":
        hr, hi = split_ri(h)
        rr, ri = split_ri(r)
        tr, ti = split_ri(t)
        s = hr * rr * tr + hi * rr * ti + hr * ri * ti - hi * ri * tr
        return ctx.psum(jnp.sum(s, axis=-1))
    if model == "rotate":
        hr, hi = split_ri(h)
        rr, ri = _phase(r, emb_scale)
        tr, ti = split_ri(t)
        or_, oi = _cmul(hr, hi, rr, ri)
        d2 = ctx.psum(jnp.sum(jnp.square(or_ - tr) + jnp.square(oi - ti), axis=-1))
        return gamma - jnp.sqrt(d2 + 1e-12)
    if model in ("transr", "rescal"):
        assert r_proj is not None and rel_dim > 0
        ds = h.shape[-1]
        m = r_proj.reshape(r_proj.shape[0], ds, rel_dim)  # this server's rows of M_r
        ph = ctx.psum(jnp.einsum("bd,bdr->br", h, m))  # (b, rel_dim) replicated
        pt = ctx.psum(jnp.einsum("bd,bdr->br", t, m))
        if model == "rescal":
            # h^T M_r t == (M_r^T h) . t ; ph is replicated, t is dim-sharded:
            # multiply this server's slice of ph with t and psum.
            del pt
            return ctx.psum(jnp.sum(_slice_replicated(ph, ctx) * t, axis=-1))
        # TransR: gamma - || M_r h + r - M_r t ||_2 ; r slice belongs to this
        # server, so compare slices of the replicated projections.
        rs = _slice_replicated(ph, ctx) + r - _slice_replicated(pt, ctx)
        d2 = ctx.psum(jnp.sum(jnp.square(rs), axis=-1))
        return gamma - jnp.sqrt(d2 + 1e-12)
    raise ValueError(model)


def _slice_replicated(x: jnp.ndarray, ctx: ShardCtx) -> jnp.ndarray:
    """Take this server's dim slice of a replicated (b, rel_dim) tensor."""
    if ctx.axis is None:
        return x
    s = ctx.size
    ds = x.shape[-1] // s
    i = ctx.index()
    return jax.lax.dynamic_slice_in_dim(x, i * ds, ds, axis=-1)


# --------------------------------------------------------------------------
# Joint-negative decomposition (T1): score(b, neg_j) = pairwise(o_b, neg_j)
# --------------------------------------------------------------------------
def neg_o(
    model: str,
    h_or_t: jnp.ndarray,  # (b, ds) the NON-corrupted entity
    r: jnp.ndarray,  # (b, rel_ds)
    corrupt: str,  # 'tail' | 'head'
    ctx: ShardCtx,
    r_proj: Optional[jnp.ndarray] = None,
    rel_dim: int = 0,
    emb_scale: float = 1.0,
) -> jnp.ndarray:
    """The per-triplet vector o with score = pairwise(o, candidate)."""
    e = h_or_t
    if model == "transe_l1":
        return e + r if corrupt == "tail" else e - r  # ||o - t'||, ||h' - o|| == ||o - h'||... see note
    if model == "transe_l2":
        return e + r if corrupt == "tail" else e - r
    if model == "distmult":
        return e * r
    if model == "complex":
        er, ei = split_ri(e)
        rr, ri = split_ri(r)
        if corrupt == "tail":
            # score(t') = dot(interleave(o), interleave(t')) with o = conj(h∘r)
            orr, oii = _cmul(er, ei, rr, ri)
            return merge_ri(orr, oii)  # dot with t' interleaved == Re(h∘r·conj(t'))
        # head corruption: score(h') = dot(h', o) with o = conj(r)∘t
        orr, oii = _cmul(er, ei, rr, -ri)
        return merge_ri(orr, oii)
    if model == "rotate":
        er, ei = split_ri(e)
        rr, ri = _phase(r, emb_scale)
        if corrupt == "tail":
            orr, oii = _cmul(er, ei, rr, ri)  # o = h∘r, dist to t'
        else:
            orr, oii = _cmul(er, ei, rr, -ri)  # o = conj(r)∘t, dist to h'
        return merge_ri(orr, oii)
    if model == "transr":
        assert r_proj is not None and rel_dim > 0
        ds = e.shape[-1]
        m = r_proj.reshape(r_proj.shape[:-1] + (ds, rel_dim))
        pe = ctx.psum(jnp.einsum("...bd,...bdr->...br", e, m))  # replicated
        if corrupt == "tail":
            return pe + _gather_full_r(r, ctx)
        return pe - _gather_full_r(r, ctx)  # replicated; negatives projected too
    if model == "rescal":
        assert r_proj is not None and rel_dim > 0
        ds = e.shape[-1]
        m = r_proj.reshape(r_proj.shape[0], ds, rel_dim)
        if corrupt == "tail":
            # score(t') = (M_r^T h) . t' — slice the replicated product
            pe = ctx.psum(jnp.einsum("bd,bdr->br", e, m))
            return _slice_replicated(pe, ctx)
        # score(h') = h' . (M_r t) — this server's d-rows of M_r times full t
        t_full = _gather_full_r(e, ctx)  # (b, rel_dim)
        return jnp.einsum("bdr,br->bd", m, t_full)  # (b, ds) sharded
    raise ValueError(model)


def _gather_full_r(r_slice: jnp.ndarray, ctx: ShardCtx) -> jnp.ndarray:
    """All-gather a (..., b, ds) dim slice into the full replicated (..., b, dim)."""
    if ctx.axis is None:
        return r_slice
    return jax.lax.all_gather(r_slice, ctx.axis, axis=r_slice.ndim - 1, tiled=True)


def pairwise_scores(
    mode: str, o: jnp.ndarray, negs: jnp.ndarray
) -> jnp.ndarray:
    """Reference pairwise reduction: (b, d) x (k, d) -> (b, k).

    ``l2sq``/``l1`` return *partial distances* (caller psums then applies
    gamma - sqrt/identity); ``dot`` returns partial dots.
    The Pallas kernel kernels/kge_score implements exactly this contract.
    """
    if mode == "dot":
        return o @ negs.T
    if mode == "l2sq":
        o2 = jnp.sum(jnp.square(o), axis=-1, keepdims=True)
        n2 = jnp.sum(jnp.square(negs), axis=-1)[None, :]
        return o2 - 2.0 * (o @ negs.T) + n2
    if mode == "l1":
        return jnp.sum(jnp.abs(o[:, None, :] - negs[None, :, :]), axis=-1)
    raise ValueError(mode)


def finish_neg_scores(
    model: str, partial: jnp.ndarray, gamma: float, ctx: ShardCtx
) -> jnp.ndarray:
    """psum partial pairwise reductions and convert to scores."""
    s = ctx.psum(partial)
    if model in ("transe_l2", "rotate", "transr"):
        return gamma - jnp.sqrt(jnp.maximum(s, 0.0) + 1e-12)
    if model == "transe_l1":
        return gamma - s
    return s  # dot-family


def negative_score_sharded(
    model: str,
    h_or_t: jnp.ndarray,  # (b, ds) dim-sharded
    r: jnp.ndarray,
    negs: jnp.ndarray,  # (k, ds) dim-sharded candidate entities
    corrupt: str,
    gamma: float,
    ctx: ShardCtx,
    emb_scale: float = 1.0,
    pairwise_fn=None,
    wire_dtype=None,  # cast o/negs for the gather (e.g. bf16 halves ICI)
):
    """Negative-sharded joint scoring (beyond-paper; EXPERIMENTS.md §Perf):

    instead of psum-ing the full (b, k) score matrix over the dim-striped
    'model' axis, all-gather the per-triplet ``o`` vectors (b×d — small) and
    re-shard the NEGATIVES over servers via all_to_all; each server then owns
    complete full-dim scores for its k/S negatives, and only scalar loss
    terms cross the wire. Supported for the elementwise-o family
    (TransE/DistMult/ComplEx/RotatE); TransR/RESCAL use ``negative_score``.

    Returns (b, k/S) *local* scores — reduce loss terms with a scalar psum.
    """
    assert model not in ("transr", "rescal")
    pw = pairwise_fn or pairwise_scores
    mode = PAIRWISE_OF[model]
    o = neg_o(model, h_or_t, r, corrupt, ctx, emb_scale=emb_scale)
    if ctx.axis is None:
        partial = pw(mode, o, negs)
        return finish_neg_scores_local(model, partial, gamma)
    cdt = o.dtype if wire_dtype is None else jnp.dtype(wire_dtype)
    o_full = jax.lax.all_gather(o.astype(cdt), ctx.axis, axis=1,
                                tiled=True).astype(o.dtype)  # (b, d)
    negs_loc = jax.lax.all_to_all(
        negs.astype(cdt), ctx.axis, split_axis=0, concat_axis=1,
        tiled=True).astype(negs.dtype)  # (k/S, d)
    partial = pw(mode, o_full, negs_loc)
    return finish_neg_scores_local(model, partial, gamma)


def finish_neg_scores_local(model: str, full: jnp.ndarray, gamma: float):
    """Like finish_neg_scores but the reduction over dim is already complete."""
    if model in ("transe_l2", "rotate", "transr"):
        return gamma - jnp.sqrt(jnp.maximum(full, 0.0) + 1e-12)
    if model == "transe_l1":
        return gamma - full
    return full


def negative_score(
    model: str,
    h_or_t: jnp.ndarray,
    r: jnp.ndarray,
    negs: jnp.ndarray,  # (k, ds) candidate entities (dim slice)
    corrupt: str,
    gamma: float,
    ctx: ShardCtx,
    r_proj: Optional[jnp.ndarray] = None,
    rel_dim: int = 0,
    emb_scale: float = 1.0,
    pairwise_fn=None,
) -> jnp.ndarray:
    """(b, k) negative scores via the joint decomposition.

    ``pairwise_fn(mode, o, negs)`` defaults to the jnp reference; the Pallas
    kernel wrapper (kernels/kge_score/ops.py) is drop-in. TransR takes no
    ``pairwise_fn`` (``projected_l2sq`` scores it) and also accepts a leading
    group axis on every input: (g, b, .), (g, k, ds), (g, b, ds * rel_dim).
    """
    pw = pairwise_fn or pairwise_scores
    mode = PAIRWISE_OF[model]
    o = neg_o(model, h_or_t, r, corrupt, ctx, r_proj, rel_dim, emb_scale)
    if model == "transr":
        # negatives must be projected per relation: (..., b, k, rel_dim)
        m = r_proj.reshape(r_proj.shape[:-1] + (negs.shape[-1], rel_dim))
        d2 = projected_l2sq(o, negs, m, ctx)
        return gamma - jnp.sqrt(d2 + 1e-12)  # already full-dim: no finish psum
    partial = pw(mode, o, negs)
    return finish_neg_scores(model, partial, gamma, ctx)


def projected_l2sq(o: jnp.ndarray, negs: jnp.ndarray, m: jnp.ndarray,
                   ctx: ShardCtx) -> jnp.ndarray:
    """TransR's negative distances: sum_r (o_b - (negs_k @ M_b)_r)^2 for
    every triplet b and shared candidate k; o (..., b, R), negs (..., k, ds),
    m (..., b, ds, R) -> (..., b, k).

    On a TPU with the dim unsharded, the ``kge.transr_score`` kernels
    (kernels/kge_score) compute it without writing the (..., b, k, R)
    projections; elsewhere, and when the projections are partial sums over
    a dim-striped axis, the einsum below does.
    """
    if ctx.axis is None and compat.backend() == "tpu":
        from repro.kernels.kge_score import transr_l2sq

        lead = o.shape[:-2]
        flat = lambda x: x.reshape((-1,) + x.shape[len(lead):])  # noqa: E731
        d2 = transr_l2sq(flat(o), flat(negs), flat(m))
        return d2.reshape(lead + d2.shape[1:])
    pn = ctx.psum(jnp.einsum("...kd,...bdr->...bkr", negs, m))  # replicated
    return jnp.sum(jnp.square(o[..., :, None, :] - pn), axis=-1)
