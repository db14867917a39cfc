"""Negative-sharded KGE scoring must equal the psum-reduced baseline path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import scores as S
from repro.common.compat import set_mesh, shard_map

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("model", ["transe_l2", "transe_l1", "distmult",
                                   "complex", "rotate"])
def test_negative_sharded_equals_psum(mesh8, model):
    """negative_score_sharded over 2 servers == unsharded negative_score."""
    b, d, k = 8, 32, 16
    h = jnp.asarray(RNG.standard_normal((b, d)).astype(np.float32) * 0.5)
    r = jnp.asarray(RNG.standard_normal((b, d)).astype(np.float32) * 0.5)
    negs = jnp.asarray(RNG.standard_normal((k, d)).astype(np.float32) * 0.5)
    ref = S.negative_score(model, h, r, negs, "tail", 10.0, S.ShardCtx(None),
                           emb_scale=1.0)

    def body(h_, r_, n_):
        out = S.negative_score_sharded(model, h_, r_, n_, "tail", 10.0,
                                       S.ShardCtx("model"), emb_scale=1.0)
        return out  # (b, k/2) local slice

    f = shard_map(body, mesh=mesh8,
                      in_specs=(P(None, "model"), P(None, "model"),
                                P(None, "model")),
                      out_specs=P(None, "model"), check_vma=False)
    with set_mesh(mesh8):
        got = jax.jit(f)(h, r, negs)
    # out_specs concatenates the k/2 slices along axis 1 in server order —
    # matching the all_to_all(split k) distribution order
    np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)

