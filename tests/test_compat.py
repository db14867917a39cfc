"""The JAX spellings that move between releases (repro/common/compat.py).

Every function is checked against a monkeypatched fake jax module of the
installed shape (exactly what compat calls), plus an integration pass
against the real installed JAX.
"""

import enum
import types

import numpy as np
import pytest

from repro.common import compat


# ----------------------------------------------------------------- fake jaxes
class _AxisType(enum.Enum):
    Auto = "auto"
    Explicit = "explicit"


class _Recorder:
    """Callable that records (args, kwargs) and returns a sentinel."""

    def __init__(self, result="result", reject=()):
        self.calls = []
        self.result = result
        self.reject = tuple(reject)

    def __call__(self, *args, **kwargs):
        for bad in self.reject:
            if bad in kwargs:
                raise TypeError(f"unexpected keyword argument {bad!r}")
        self.calls.append((args, kwargs))
        return self.result


class _CtxRecorder:
    """Context-manager factory recording enter/exit."""

    def __init__(self):
        self.entered = []
        self.exited = []

    def __call__(self, mesh):
        rec = self

        class _Ctx:
            def __enter__(self):
                rec.entered.append(mesh)
                return mesh

            def __exit__(self, *exc):
                rec.exited.append(mesh)
                return False

        return _Ctx()


def fake_new_jax():
    """The installed shape: AxisType, make_mesh(axis_types=), jax.shard_map
    with check_vma, jax.set_mesh, jax.lax.axis_size."""
    jx = types.SimpleNamespace()
    jx.__version__ = "0.9.0"
    jx.sharding = types.SimpleNamespace(AxisType=_AxisType)
    jx.make_mesh = _Recorder(result="new-mesh")
    jx.shard_map = _Recorder(result="new-mapped", reject=("check_rep",))
    jx.set_mesh = _CtxRecorder()
    jx.lax = types.SimpleNamespace(axis_size=_Recorder(result=4))
    jx.default_backend = lambda: "tpu"
    return jx


@pytest.fixture(params=["new"])
def fake(request, monkeypatch):
    jx = fake_new_jax()
    monkeypatch.setattr(compat, "jax", jx)
    return request.param, jx


# ------------------------------------------------------------ installed shape
def test_make_mesh_both_shapes(fake):
    kind, jx = fake
    mesh = compat.make_mesh((4, 2), ("data", "model"))
    assert mesh == f"{kind}-mesh"
    (args, kwargs), = jx.make_mesh.calls
    assert args == ((4, 2), ("data", "model"))
    assert kwargs == {"axis_types": (_AxisType.Auto, _AxisType.Auto)}


def test_shard_map_both_shapes(fake):
    kind, jx = fake

    def body(x):
        return x

    out = compat.shard_map(body, mesh="m", in_specs="i", out_specs="o",
                           check_vma=False)
    assert out == f"{kind}-mapped"
    (args, kwargs), = jx.shard_map.calls
    assert args == (body,)
    assert kwargs["mesh"] == "m"
    assert kwargs["in_specs"] == "i" and kwargs["out_specs"] == "o"
    assert kwargs["check_vma"] is False


def test_capability_probe_both_shapes(fake):
    kind, jx = fake
    assert compat.backend() == "tpu"
    # kernels interpret exactly when there is no TPU
    assert compat.interpret_kernels() is False
    jx.default_backend = lambda: "cpu"
    assert compat.interpret_kernels() is True
    assert compat.axis_size("data") == 4
    (args, _), = jx.lax.axis_size.calls
    assert args == ("data",)


def test_set_mesh_new_uses_setter(monkeypatch):
    jx = fake_new_jax()
    monkeypatch.setattr(compat, "jax", jx)
    with compat.set_mesh("the-mesh") as m:
        assert m == "the-mesh"
        assert jx.set_mesh.entered == ["the-mesh"]
    assert jx.set_mesh.exited == ["the-mesh"]


# -------------------------------------------------------------- cost analysis
class _Compiled:
    def __init__(self, raw):
        self.raw = raw

    def cost_analysis(self):
        return self.raw


@pytest.mark.parametrize("raw,want", [
    ({"flops": 10.0, "bytes accessed": 5.0},
     {"flops": 10.0, "bytes accessed": 5.0}),
    (None, {}),                                         # no implementation
])
def test_cost_analysis_normalizes_both_shapes(raw, want):
    assert compat.cost_analysis(_Compiled(raw)) == want


# ----------------------------------------------------------- real-jax contract
def test_real_make_mesh_and_shard_map(mesh8):
    """Integration: the installed JAX passes through compat."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    assert tuple(mesh8.axis_names) == ("data", "model")
    f = compat.shard_map(
        lambda x: jax.lax.psum(x, "data"), mesh=mesh8,
        in_specs=P("data"), out_specs=P(None), check_vma=False)
    with compat.set_mesh(mesh8):
        out = jax.jit(f)(jnp.arange(8.0))
    want = np.arange(8.0).reshape(4, 2).sum(axis=0)  # psum over the 4 blocks
    np.testing.assert_allclose(np.asarray(out), want)


def test_real_cost_analysis_is_dict():
    import jax
    import jax.numpy as jnp

    compiled = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    ca = compat.cost_analysis(compiled)
    assert isinstance(ca, dict) and ca.get("flops", 0) > 0


def test_real_backend_probe():
    assert compat.backend() in ("cpu", "gpu", "tpu")
    assert compat.interpret_kernels() == (compat.backend() != "tpu")
