"""Step builders on a real (8-CPU-device) mesh: train with microbatching +
FSDP, serve with sharded caches, and abstract-args consistency."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import InputShape
from repro.configs import ARCHS
from repro.models.steps import (
    build_serve_step, build_train_step, effective_microbatches, input_defs,
    serve_abstract_args, train_abstract_args,
)
from repro.models.transformer import build_model
from repro.common.compat import cost_analysis, set_mesh

RNG = np.random.default_rng(0)


def _reduced_mesh_cfg(name, mesh, **kw):
    cfg = ARCHS[name].reduced()
    # reduced() turns scan off; multi-group scan path needs >=2 groups
    cfg = dataclasses.replace(cfg, **kw)
    return cfg


def test_train_step_on_mesh(mesh8):
    shape = InputShape("t", 32, 16, "train")
    cfg = _reduced_mesh_cfg("qwen1.5-0.5b", mesh8, microbatches=2,
                            scan_layers=True, n_layers=4, remat=True)
    model = build_model(cfg, mesh=mesh8)
    step, opt = build_train_step(model, shape=shape)
    with set_mesh(mesh8):
        params = model.init(jax.random.key(0))
        opt_state = opt.init(params)
        bdefs = input_defs(cfg, shape, model)
        batch = {k: jnp.asarray(RNG.integers(0, cfg.vocab_size, d.shape), d.dtype)
                 for k, d in bdefs.items()}
        jstep = jax.jit(step, donate_argnums=(0, 1))
        p2, o2, m = jstep(params, opt_state, batch)
        p3, o3, m2 = jstep(p2, o2, batch)
    assert np.isfinite(float(m["loss"])) and np.isfinite(float(m2["loss"]))
    assert float(m2["loss"]) != float(m["loss"])  # params actually moved


def test_train_step_fsdp_moe(mesh8):
    shape = InputShape("t", 32, 8, "train")
    cfg = _reduced_mesh_cfg("mixtral-8x7b", mesh8, microbatches=2, fsdp=True,
                            capacity_factor=4.0)
    model = build_model(cfg, mesh=mesh8)
    step, opt = build_train_step(model, shape=shape)
    with set_mesh(mesh8):
        params = model.init(jax.random.key(0))
        opt_state = opt.init(params)
        bdefs = input_defs(cfg, shape, model)
        batch = {k: jnp.asarray(RNG.integers(0, cfg.vocab_size, d.shape), d.dtype)
                 for k, d in bdefs.items()}
        p2, o2, m = jax.jit(step)(params, opt_state, batch)
    assert np.isfinite(float(m["loss"]))


def test_serve_step_on_mesh(mesh8):
    cfg = _reduced_mesh_cfg("h2o-danube-1.8b", mesh8)
    model = build_model(cfg, mesh=mesh8)
    serve = build_serve_step(model)
    with set_mesh(mesh8):
        params = model.init(jax.random.key(0))
        caches = jax.tree.map(lambda d: jnp.zeros(d.shape, d.dtype),
                              model.cache_defs(8, 64),
                              is_leaf=lambda x: hasattr(x, "materialize"))
        token = jnp.zeros((8, 1), jnp.int32)
        lg, caches = jax.jit(serve)(params, caches, token,
                                    jnp.asarray(0, jnp.int32))
    assert np.isfinite(np.asarray(lg, np.float32)).all()


def test_effective_microbatches():
    shape = InputShape("t", 128, 256, "train")

    class FakeModel:
        mesh = None
        batch_axes = None

    cfg = dataclasses.replace(ARCHS["qwen1.5-0.5b"], microbatches=8)
    assert effective_microbatches(cfg, shape, FakeModel()) == 8
    shape1 = InputShape("d", 128, 256, "decode")
    assert effective_microbatches(cfg, shape1, FakeModel()) == 1


def test_abstract_args_lower(mesh8):
    """AOT lowering from pure ShapeDtypeStructs (the dry-run path) on the
    test mesh, for a reduced arch — fast end-to-end check."""
    shape = InputShape("t", 64, 16, "train")
    cfg = _reduced_mesh_cfg("mamba2-2.7b", mesh8, microbatches=2)
    model = build_model(cfg, mesh=mesh8)
    step, _ = build_train_step(model, shape=shape)
    aps, aos, batch = train_abstract_args(model, shape)
    with set_mesh(mesh8):
        compiled = jax.jit(step).lower(aps, aos, batch).compile()
    assert cost_analysis(compiled)
