"""Ring-attention tests on the 8-fake-device CPU mesh (SURVEY.md §4.6):
the sharded ring must equal dense attention over the gathered sequence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.manifolds import Lorentz
from hyperspace_tpu.nn.attention import lorentz_attention
from hyperspace_tpu.parallel.mesh import make_mesh
from hyperspace_tpu.parallel.ring import ring_attention_sharded


@pytest.fixture(scope="module")
def mesh8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_mesh({"seq": 8})


def _pts(key, m, shape):
    return m.random_normal(key, shape, jnp.float64)


@pytest.mark.parametrize("L", [
    32, pytest.param(64, marks=pytest.mark.slow)])
def test_ring_matches_dense(mesh8, L):
    m = Lorentz(1.0)
    q = _pts(jax.random.PRNGKey(0), m, (2, L, 7))
    k = _pts(jax.random.PRNGKey(1), m, (2, L, 7))
    v = _pts(jax.random.PRNGKey(2), m, (2, L, 7))
    dense = lorentz_attention(q, k, v, m, beta=0.2, tau=1.3)
    ring = ring_attention_sharded(q, k, v, m, mesh8, "seq", beta=0.2, tau=1.3)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                               rtol=1e-9, atol=1e-11)


@pytest.mark.slow
def test_ring_under_jit_compiles_collectives(mesh8):
    """The sharded ring must jit as one program (collectives inside XLA)."""
    m = Lorentz(0.5)
    q = _pts(jax.random.PRNGKey(3), m, (1, 16, 5))

    @jax.jit
    def f(q):
        return ring_attention_sharded(q, q, q, m, mesh8, "seq")

    out = f(q)
    assert out.shape == q.shape
    assert float(jnp.max(m.check_point(out))) < 1e-8
    # grads flow through ppermute
    g = jax.grad(lambda q: jnp.sum(f(q)[..., 1:] ** 2))(q)
    assert bool(jnp.isfinite(g).all())


def test_ring_with_key_padding_mask_matches_dense(mesh8):
    """Masked ring == dense attention with the same key-padding mask (the
    long-context path must support padded batches, not just packed ones)."""
    m = Lorentz(1.0)
    L = 32
    q = _pts(jax.random.PRNGKey(4), m, (2, L, 7))
    rng = np.random.default_rng(0)
    k_mask = jnp.asarray(rng.random((2, L)) > 0.3)
    dense = lorentz_attention(q, q, q, m, mask=k_mask[:, None, :])
    ring = ring_attention_sharded(q, q, q, m, mesh8, "seq", k_mask=k_mask)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                               rtol=1e-9, atol=1e-11)


def test_ring_body_direct_shard_map_unmasked(mesh8):
    """ring_lorentz_attention with k_mask=None must work inside a caller's
    own shard_map (no mask in the loop carry — regression for the
    varying-type carry mismatch)."""
    from functools import partial as fpartial

    from jax import shard_map
    from hyperspace_tpu.parallel.ring import ring_lorentz_attention
    from jax.sharding import PartitionSpec as P

    m = Lorentz(1.0)
    q = _pts(jax.random.PRNGKey(6), m, (2, 32, 7))
    spec = P(None, "seq", None)

    @fpartial(shard_map, mesh=mesh8, in_specs=(spec,), out_specs=spec)
    def run(q):
        return ring_lorentz_attention(q, q, q, m, "seq")

    dense = lorentz_attention(q, q, q, m)
    np.testing.assert_allclose(np.asarray(run(q)), np.asarray(dense),
                               rtol=1e-9, atol=1e-11)


def test_ring_backward_does_not_save_score_tiles(mesh8):
    """The ring loop remats each hop (r04): reverse-mode AD must not
    stack per-hop [Lq_loc, Lk_loc] score tiles across the n ring steps —
    the grad jaxpr may contain nothing of size >= n*Lq_loc*Lk_loc."""
    mesh = mesh8
    n = 8
    L, D = 1024, 8          # Lq_loc = Lk_loc = 128 per device
    m = Lorentz(1.0)
    rng = np.random.default_rng(0)
    sp = rng.standard_normal((1, L, D)).astype(np.float32) * 0.3
    t = np.sqrt(1.0 + np.sum(sp * sp, axis=-1, keepdims=True))
    q = jnp.asarray(np.concatenate([t, sp], axis=-1))

    def loss(q):
        out = ring_attention_sharded(q, q, q, m, mesh, axis="seq")
        return jnp.sum(out[..., 1:] ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss))(q)

    def sizes(jx):
        for eqn in jx.eqns:
            for var in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(var, "aval", None)
                if aval is not None and hasattr(aval, "shape"):
                    yield int(np.prod(aval.shape)) if aval.shape else 1
            for param in eqn.params.values():
                for sub in jax.tree_util.tree_leaves(
                        param, is_leaf=lambda x: isinstance(
                            x, jax.extend.core.ClosedJaxpr)):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        yield from sizes(sub.jaxpr)

    lq = L // n
    biggest = max(sizes(jaxpr.jaxpr))
    assert biggest < n * lq * lq, biggest  # stacked tiles would be 8*128*128
