"""The JAX spellings that move between releases, in one module.

The repo runs on one installation: JAX 0.9.0 (jaxlib 0.9.0, libtpu 0.0.34).
Mesh construction, the ambient mesh, ``shard_map``, ``axis_size`` and the
cost-analysis result have each changed name or shape across recent
releases; call sites import them from here, so a future upgrade is fixed in
this file and nowhere else.

Rule (enforced by tests/test_compat.py and CI grep): no such JAX symbol
outside this module.

All functions resolve through the module-level ``jax`` reference, so tests
can monkeypatch a fake module and check exactly what is called.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax


# --------------------------------------------------------------- capabilities
def backend() -> str:
    """Default platform: 'cpu' | 'gpu' | 'tpu'."""
    return jax.default_backend()


def interpret_kernels() -> bool:
    """Whether Pallas kernels run in interpret mode: exactly when there is
    no TPU. On a TPU backend every kernel is compiled by Mosaic.

    This is the single switch for every kernel wrapper in repro.kernels —
    kernels ask the compat layer, never the backend directly. Entry points
    print the platform they run on (launch/train.py, chip_smoke.py), so a
    CPU run is never mistaken for a chip run.
    """
    return backend() != "tpu"


# --------------------------------------------------------------------- meshes
def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """A device mesh whose axes all have the ``Auto`` sharding type (the
    behaviour every program here is written for)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(int(s) for s in shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def set_mesh(mesh):
    """Context manager entering ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


# ------------------------------------------------------------------ shard_map
def shard_map(f=None, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with its replication check spelled ``check_vma``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma)


# ---------------------------------------------------------------- collectives
def axis_size(axis_name):
    """Size of a named mesh axis (or tuple of axes) inside a mapped body."""
    return jax.lax.axis_size(axis_name)


def all_to_all(x, axis_name, split_axis: int, concat_axis: int, *,
               tiled: bool = True):
    """The KVStore wire primitive, pinned here so remote pull/push has one
    audited entry point if the lax collective API moves again."""
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=tiled)


# -------------------------------------------------------------- cost analysis
def cost_analysis(compiled) -> Dict[str, Any]:
    """XLA cost analysis of a ``Compiled`` as a flat dict ({} for backends
    without an implementation, where JAX returns None)."""
    return dict(compiled.cost_analysis() or {})
