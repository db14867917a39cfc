"""Plain reference of TransR training, independent of the program.

Straightforward ``jax.numpy`` at float32 with every matrix product at
``HIGHEST`` precision, written from the definition (Lin et al. 2015):
score(h, r, t) = gamma - ||h M_r + r - t M_r||_2, with M_r the relation's
(dim, rel_dim) projection. Every (triplet, candidate) pair of a negative
group is projected explicitly by the triplet's own M_r: no joint
decomposition. Then the loss, autodiff gradients against the whole entity,
relation and projection tables, and dense Adagrad (untouched rows get a zero
gradient and so do not move). Weights are drawn from the seed by the
initialisation the configuration states: entity and relation rows uniform in
(-s, s), s = (gamma + 2) / dim, and each projection the identity plus 0.1 *
U(-s, s), from three splits of the seed's key. Called with
``dtype=jnp.bfloat16`` it is the control: the same mathematics one precision
below the configuration's float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("tail", "head")
ADAGRAD_EPS = 1e-10


def init_tables(kge: dict, seed: int, dtype=jnp.float32) -> dict:
    d, rd = kge["dim"], kge["rel_dim"] or kge["dim"]
    s = (kge["gamma"] + 2.0) / d
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    n_rel = kge["n_relations"]
    ent = jax.random.uniform(k1, (kge["n_entities"], d), jnp.float32, -s, s)
    rel = jax.random.uniform(k2, (n_rel, rd), jnp.float32, -s, s)
    proj = (jax.random.uniform(k3, (n_rel, d * rd), jnp.float32, -s, s) * 0.1
            + np.eye(d, rd, dtype=np.float32).reshape(-1))
    return {"entity": ent.astype(dtype), "rel": rel.astype(dtype),
            "proj": proj.astype(dtype)}


def _project(e, m):
    """e (..., d) times its own M (..., d, rel_dim)."""
    return jnp.einsum("...d,...dr->...r", e, m, precision=HIGHEST)


def loss(kge: dict, params: dict, batch: dict):
    """Mean loss of one joint-negative batch: h, r, t (b,), neg (2, ng, k)."""
    gamma, d = kge["gamma"], kge["dim"]
    ent, rel, proj = params["entity"], params["rel"], params["proj"]
    b = batch["h"].shape[0]
    h, t, r = ent[batch["h"]], ent[batch["t"]], rel[batch["r"]]
    m = proj[batch["r"]].reshape(b, d, -1)
    ph, pt = _project(h, m), _project(t, m)
    pos = gamma - jnp.sqrt(jnp.sum(jnp.square(ph + r - pt), axis=-1))
    n_groups = batch["neg"].shape[1]
    gsz = b // n_groups
    negs = []
    for mode, corrupt in enumerate(MODES):
        for g in range(n_groups):
            sl = slice(g * gsz, (g + 1) * gsz)
            cands = ent[batch["neg"][mode, g]]  # (k, d)
            # each candidate projected by each triplet's M_r: (gsz, k, rel_dim)
            pc = jnp.einsum("kd,bdr->bkr", cands, m[sl], precision=HIGHEST)
            if corrupt == "tail":  # h M + r - t' M
                diff = (ph + r)[sl][:, None, :] - pc
            else:  # h' M + r - t M
                diff = pc + (r - pt)[sl][:, None, :]
            negs.append(gamma - jnp.sqrt(jnp.sum(jnp.square(diff), axis=-1)))
    neg = jnp.concatenate(negs)  # (2b, k)
    lp = jnp.mean(jax.nn.softplus(-pos))
    if kge["loss"] == "self_adv":
        w = jax.nn.softmax(jax.lax.stop_gradient(neg), axis=-1)
        return lp + jnp.mean(jnp.sum(w * jax.nn.softplus(neg), axis=-1))
    if kge["loss"] == "logistic":
        return lp + jnp.mean(jax.nn.softplus(neg))
    raise ValueError(f"no reference for loss {kge['loss']!r}")


@functools.partial(jax.jit, static_argnums=0)
def _step(kge_items, params, gsq, batch):
    kge = dict(kge_items)
    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(functools.partial(loss, kge))(params, batch)
        new_gsq = {k: gsq[k] + jnp.square(grads[k]) for k in params}
        new_params = {k: params[k] - kge["lr"] * grads[k]
                      / (jnp.sqrt(new_gsq[k]) + ADAGRAD_EPS) for k in params}
        norms = {k: jnp.linalg.norm(grads[k].astype(jnp.float32)) for k in grads}
    return new_params, new_gsq, value.astype(jnp.float32), norms


def train(kge: dict, seed: int, batches: list, dtype=jnp.float32) -> dict:
    """Run the given batches from the seed's weights. Returns each step's loss,
    each table's first gradient norm and each table's change after the last
    batch."""
    if kge["model"] != "transr":
        raise ValueError(f"this reference is TransR's, not {kge['model']!r}")
    params = init_tables(kge, seed, dtype)
    start = {k: v.astype(jnp.float32) for k, v in params.items()}
    gsq = {k: jnp.zeros_like(v) for k, v in params.items()}
    items = tuple(sorted((k, v) for k, v in kge.items() if not isinstance(v, (dict, list))))
    losses, grad_norms = [], None
    for b in batches:
        dev = {k: jnp.asarray(v, jnp.int32) for k, v in b.items()}
        params, gsq, value, norms = _step(items, params, gsq, dev)
        losses.append(float(value))
        if grad_norms is None:
            grad_norms = {k: float(v) for k, v in norms.items()}
    change = {k: float(jnp.linalg.norm(params[k].astype(jnp.float32) - start[k]))
              for k in params}
    return {"loss": losses, "grad_norm": grad_norms, "change_norm": change}
