"""Production meshes.

Single pod: (16, 16) = ('data', 'model')  — 256 chips (TPU v5e pod).
Multi-pod:  (2, 16, 16) = ('pod', 'data', 'model') — 512 chips.

The pod meshes are the targets of ``launch/dryrun.py --kge``. A FUNCTION,
not a module constant: importing this module must never touch jax device
state (tests force 8 CPU devices in tests/conftest.py; only launch/dryrun.py
sets the 512-device count).

Mesh construction is version-sensitive (axis-type keywords came and went);
all of it goes through repro.common.compat.
"""

from __future__ import annotations

from repro.common import compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/examples (e.g. (4, 2) on 8 CPU devices)."""
    return compat.make_mesh(shape, axes)
