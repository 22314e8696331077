"""The LP decoder's pair distances (nn/edge_dist.py): values and gradients —
including learned-curvature cotangents — must match the direct
``m.sqdist(z[a], z[b])`` formulation exactly (the reorganized scatter is
algebraically the same sum)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.nn.gcn import make_manifold


# --- pair_sqdist: any pairs, fresh on every step, nothing from the host -------


def _pairs_with_a_hub(rng, n, p):
    u = rng.integers(0, n, p).astype(np.int32)
    v = rng.integers(0, n, p).astype(np.int32)
    u[: p // 3] = n // 2  # a hub, and pairs that repeat
    v[p // 2: p // 2 + 5] = u[p // 2: p // 2 + 5]  # both ends one node
    return jnp.asarray(u), jnp.asarray(v)


@pytest.mark.parametrize("mode", ["xla", "interpret"])
@pytest.mark.parametrize("dtype", ["float64", "float32"])
@pytest.mark.parametrize("kind", ["lorentz", "poincare"])
def test_pair_sqdist_matches_direct(kind, dtype, mode, rng, monkeypatch):
    """Values, dz and the learned curvature's cotangent against plain
    ``m.sqdist(z[u], z[v])`` autodiff: the same per-pair rows, summed in
    another order."""
    from hyperspace_tpu.nn.edge_dist import pair_sqdist

    monkeypatch.setenv("HYPERSPACE_KERNELS", mode)
    n, p = 300, 1100
    m = make_manifold(kind, 0.8)
    z = m.random_normal(jax.random.PRNGKey(2), (n, m.ambient_dim(8)),
                        dtype, std=0.3)
    u, v = _pairs_with_a_hub(rng, n, p)
    t = jnp.asarray(rng.standard_normal(p), dtype)
    c = jnp.asarray(0.8, dtype)

    def loss_sorted(z, c):
        return jnp.sum(pair_sqdist(z, c, u, v, kind) * t)

    def loss_direct(z, c):
        return jnp.sum(make_manifold(kind, c).sqdist(z[u], z[v]) * t)

    # the kernel accumulates in float32 whatever it is given
    tight = dtype == "float64" and mode == "xla"
    rtol, atol = (1e-9, 1e-11) if tight else (2e-4, 2e-5)
    np.testing.assert_allclose(loss_sorted(z, c), loss_direct(z, c),
                               rtol=1e-12 if dtype == "float64" else 1e-6)
    gz1, gc1 = jax.grad(loss_sorted, argnums=(0, 1))(z, c)
    gz2, gc2 = jax.grad(loss_direct, argnums=(0, 1))(z, c)
    assert gz1.dtype == z.dtype and gz1.shape == z.shape
    np.testing.assert_allclose(np.asarray(gz1), np.asarray(gz2),
                               rtol=rtol, atol=atol * float(jnp.max(jnp.abs(gz2))))
    np.testing.assert_allclose(float(gc1), float(gc2),
                               rtol=1e-9 if dtype == "float64" else 1e-4)


@pytest.mark.parametrize("mode", ["xla", "interpret"])
@pytest.mark.parametrize("kind", ["lorentz", "poincare"])
def test_pair_sqdist_bf16_accumulates_in_float32(kind, mode, rng,
                                                 monkeypatch):
    """At bfloat16 lanes the oracle is NOT plain autodiff, whose
    scatter-add rounds to bfloat16 after every row: it is the same
    bfloat16 rows (sqdist's VJP at the gathered points) summed in float32
    and cast once — the contract `nn/scatter._sorted_segsum` states."""
    from hyperspace_tpu.nn.edge_dist import pair_sqdist

    monkeypatch.setenv("HYPERSPACE_KERNELS", mode)
    n, p, bf16 = 300, 1100, jnp.bfloat16
    m = make_manifold(kind, 1.0)
    z = m.random_normal(jax.random.PRNGKey(3), (n, m.ambient_dim(8)),
                        jnp.float32, std=0.3).astype(bf16)
    u, v = _pairs_with_a_hub(rng, n, p)
    t = jnp.asarray(rng.standard_normal(p), bf16)
    c = jnp.asarray(1.0, jnp.float32)
    got = jax.grad(lambda zz: jnp.sum(
        pair_sqdist(zz, c, u, v, kind).astype(jnp.float32)
        * t.astype(jnp.float32)))(z)
    assert got.dtype == bf16
    _, vjp = jax.vjp(lambda a, b: make_manifold(kind, c).sqdist(a, b),
                     z[u], z[v])
    gu, gv = vjp(t)
    want = jax.ops.segment_sum(
        jnp.concatenate([gu, gv]).astype(jnp.float32),
        jnp.concatenate([u, v]), n)
    scale = float(jnp.max(jnp.abs(want)))
    # the same rows, a float32 sum in another order, one cast: one
    # bfloat16 ulp (2**-8) where the sum falls beside a rounding boundary
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=2**-7, atol=1e-5 * scale)
    # and plain bfloat16 autodiff is the coarser of the two on the hub
    plain = jax.grad(lambda zz: jnp.sum(
        m.sqdist(zz[u], zz[v]).astype(jnp.float32)
        * t.astype(jnp.float32)))(z)
    hub = n // 2
    err = lambda g: float(jnp.linalg.norm(
        g[hub].astype(jnp.float32) - want[hub]))
    assert err(got) <= err(plain) + 1e-6
