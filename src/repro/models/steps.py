"""Step builders for the architecture zoo: train / prefill / serve.

Each builder returns (fn, in_shardings-ready abstract args) so launch/dryrun
can ``jit(fn).lower(*abstract).compile()`` without allocating anything, and
launch/train can run the same program with real arrays.

train_step: grad-accumulation over microbatches (lax.scan), fp32 grad buffer
sharded like the params (FSDP-friendly), then the config's optimizer.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common.config import ArchConfig, InputShape
from repro.models.layers import ParamDef, abstract, is_def, specs
from repro.models.transformer import Model
from repro.optim.api import make_optimizer


# ------------------------------------------------------------- optimizer specs
def opt_state_specs(name: str, param_specs, abstract_params):
    def pad(spec, rank):
        t = tuple(spec)
        return t + (None,) * (rank - len(t))

    if name == "adamw":
        return {"step": P(), "m": param_specs, "v": param_specs}
    if name == "sgd":
        return {"step": P()}
    if name == "adafactor":
        def leaf(spec, ap):
            r = len(ap.shape)
            s = pad(spec, r)
            if r >= 2:
                return {"vr": P(*s[:-1]), "vc": P(*(s[:-2] + s[-1:]))}
            return {"v": P(*s)}

        stats = jax.tree.map(leaf, param_specs, abstract_params,
                             is_leaf=lambda x: isinstance(x, P))
        return {"step": P(), "stats": stats}
    raise ValueError(name)


def _shardings(mesh, tree_specs):
    if mesh is None:
        return None
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


# ----------------------------------------------------------------- input specs
def n_machines_of(model: Model) -> int:
    if model.mesh is None or not model.batch_axes:
        return 1
    import numpy as np

    return int(np.prod([model.mesh.shape[a] for a in model.batch_axes]))


def effective_microbatches(cfg: ArchConfig, shape: InputShape, model: Model) -> int:
    """Largest grad-accum factor <= cfg.microbatches with each microbatch
    still divisible across the machine axis."""
    if shape.kind != "train":
        return 1
    machines = n_machines_of(model)
    mb = min(cfg.microbatches, max(1, shape.global_batch // machines))
    while shape.global_batch % mb or (shape.global_batch // mb) % machines:
        mb -= 1
    return max(1, mb)


def input_defs(cfg: ArchConfig, shape: InputShape, model: Model,
               microbatches: int = 0) -> Dict[str, ParamDef]:
    """ShapeDtype stand-ins for every model input of this (arch, shape)."""
    ba = model.batch_axes
    gb, T = shape.global_batch, shape.seq_len
    mb = microbatches or effective_microbatches(cfg, shape, model)
    out: Dict[str, ParamDef] = {}

    if shape.kind in ("train", "prefill"):
        tshape = (gb, T) if mb == 1 else (mb, gb // mb, T)
        tspec = P(ba, None) if mb == 1 else P(None, ba, None)
        out["tokens"] = ParamDef(tshape, tspec, init="zeros", dtype=jnp.int32)
        if shape.kind == "train":
            out["labels"] = ParamDef(tshape, tspec, init="zeros", dtype=jnp.int32)
        if cfg.frontend.value == "vision":
            nf = min(cfg.n_frontend_tokens, T)
            fshape = (gb, nf, cfg.d_model) if mb == 1 else (mb, gb // mb, nf, cfg.d_model)
            fspec = P(ba, None, None) if mb == 1 else P(None, ba, None, None)
            out["patch_embeds"] = ParamDef(fshape, fspec, init="zeros",
                                           dtype=jnp.dtype(cfg.dtype))
        if cfg.enc_dec:
            eshape = (gb, cfg.encoder_ctx, cfg.d_model) if mb == 1 else (
                mb, gb // mb, cfg.encoder_ctx, cfg.d_model)
            espec = P(ba, None, None) if mb == 1 else P(None, ba, None, None)
            out["enc_frames"] = ParamDef(eshape, espec, init="zeros",
                                         dtype=jnp.dtype(cfg.dtype))
    else:  # decode
        machines = 1
        if model.mesh is not None and ba:
            import numpy as np

            machines = int(np.prod([model.mesh.shape[a] for a in ba]))
        bspec = ba if gb % machines == 0 and gb >= machines else None
        out["token"] = ParamDef((gb, 1), P(bspec, None), init="zeros",
                                dtype=jnp.int32)
    return out


def abstract_inputs(defs: Dict[str, ParamDef], mesh):
    out = {}
    for k, d in defs.items():
        sh = NamedSharding(mesh, d.spec) if mesh is not None else None
        out[k] = jax.ShapeDtypeStruct(d.shape, d.dtype, sharding=sh)
    return out


# ------------------------------------------------------------------ train step
def build_train_step(model: Model, lr: float = 1e-4, shape: Optional[InputShape] = None):
    cfg = model.cfg
    opt = make_optimizer(cfg.optimizer, lr)
    mb = effective_microbatches(cfg, shape, model) if shape is not None else cfg.microbatches

    def pin(g):
        # Constrain per-microbatch grads to the PARAM sharding immediately:
        # GSPMD then reduce-scatters the data-parallel gradient reduction
        # into the FSDP layout instead of all-reducing the full weight grad
        # and slicing (half the ICI bytes, no full-size grad materialized) —
        # EXPERIMENTS.md §Perf hillclimb 2.
        if model.mesh is None:
            return g
        return jax.tree.map(
            lambda x, sp: jax.lax.with_sharding_constraint(
                x, NamedSharding(model.mesh, sp)),
            g, model.param_specs(),
            is_leaf=lambda x: isinstance(x, P) or hasattr(x, "dtype"))

    def train_step(params, opt_state, batch):
        if mb == 1:
            loss, grads = jax.value_and_grad(model.loss)(params, batch)
            grads = pin(grads)
        else:
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def body(acc, mbatch):
                l, g = jax.value_and_grad(model.loss)(params, mbatch)
                g = pin(g)
                return jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), acc, g), l

            grads, losses = jax.lax.scan(body, zeros, batch)
            grads = jax.tree.map(lambda g: g / mb, grads)
            loss = jnp.mean(losses)
        new_params, new_state = opt.update(params, grads, opt_state)
        return new_params, new_state, {"loss": loss}

    return train_step, opt


def train_abstract_args(model: Model, shape: InputShape, lr: float = 1e-4):
    """(abstract params, opt_state, batch) with shardings — for AOT lowering."""
    cfg = model.cfg
    mesh = model.mesh
    aps = model.abstract_params()
    pspecs = model.param_specs()

    _, opt = build_train_step(model, lr, shape)
    aos = jax.eval_shape(opt.init, aps)
    ospecs = opt_state_specs(cfg.optimizer, pspecs, aps)

    def attach(tree, spec_tree):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=NamedSharding(mesh, s) if mesh is not None else None),
            tree, spec_tree, is_leaf=lambda x: isinstance(x, P) or hasattr(x, "shape"))

    aps_s = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=NamedSharding(mesh, s) if mesh is not None else None),
        aps, pspecs, is_leaf=lambda x: isinstance(x, P))
    del attach
    # opt state specs tree matches aos structure
    aos_s = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=NamedSharding(mesh, s) if mesh is not None else None),
        aos, ospecs, is_leaf=lambda x: isinstance(x, P))
    bdefs = input_defs(model.cfg, shape, model)
    batch = abstract_inputs(bdefs, mesh)
    return aps_s, aos_s, batch


# ------------------------------------------------------- prefill / serve steps
def build_prefill_step(model: Model, use_flash: bool = False):
    def prefill(params, inputs):
        return model.forward(params, inputs, use_flash=use_flash)

    return prefill


def build_serve_step(model: Model):
    def serve(params, caches, token, index):
        return model.decode_step(params, caches, token, index)

    return serve


def serve_abstract_args(model: Model, shape: InputShape):
    mesh = model.mesh
    aps = model.abstract_params()
    pspecs = model.param_specs()
    aps_s = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=NamedSharding(mesh, s) if mesh is not None else None),
        aps, pspecs, is_leaf=lambda x: isinstance(x, P))
    cdefs = model.cache_defs(shape.global_batch, shape.seq_len)
    caches = abstract_inputs_tree(cdefs, mesh)
    idefs = input_defs(model.cfg, shape, model)
    token = abstract_inputs(idefs, mesh)["token"]
    index = jax.ShapeDtypeStruct((), jnp.int32)
    return aps_s, caches, token, index


def abstract_inputs_tree(defs, mesh):
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(
            d.shape, d.dtype,
            sharding=NamedSharding(mesh, d.spec) if mesh is not None else None),
        defs, is_leaf=is_def)
