"""Pure-jnp oracle for the kge_score kernel.

Contract (identical to core/scores.pairwise_scores):
    (B, D) x (K, D) -> (B, K)
    dot   : o @ negs.T
    l2sq  : ||o_i - n_j||^2        (partial, pre-psum)
    l1    : sum_d |o_id - n_jd|    (partial, pre-psum)

TransR's projected form (ops.transr_l2sq), (G, B, R) x (G, K, D) x
(G, B, D, R) -> (G, B, K):
    sum_r (o_b - negs_k @ M_b)_r^2
"""

from __future__ import annotations

import jax.numpy as jnp


def pairwise_ref(mode: str, o: jnp.ndarray, negs: jnp.ndarray) -> jnp.ndarray:
    if mode == "dot":
        return o @ negs.T
    if mode == "l2sq":
        o2 = jnp.sum(jnp.square(o), axis=-1, keepdims=True)
        n2 = jnp.sum(jnp.square(negs), axis=-1)[None, :]
        return o2 - 2.0 * (o @ negs.T) + n2
    if mode == "l1":
        return jnp.sum(jnp.abs(o[:, None, :] - negs[None, :, :]), axis=-1)
    raise ValueError(mode)


def l1_grads_ref(o, negs, g):
    """VJP oracle for l1: d_o (B,D), d_negs (K,D)."""
    s = jnp.sign(o[:, None, :] - negs[None, :, :])  # (B,K,D)
    d_o = jnp.einsum("bk,bkd->bd", g, s)
    d_n = -jnp.einsum("bk,bkd->kd", g, s)
    return d_o, d_n


def transr_l2sq_ref(o, negs, m):
    """Oracle for ops.transr_l2sq: every candidate projected by every
    triplet's matrix, (G, B, K, R) materialized."""
    pn = jnp.einsum("gkd,gbdr->gbkr", negs, m)
    return jnp.sum(jnp.square(o[:, :, None, :] - pn), axis=-1)
