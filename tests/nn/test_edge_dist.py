"""The LP decoder's pair distances (nn/edge_dist.py): values and gradients —
including learned-curvature cotangents — must match the direct
``m.sqdist(z[a], z[b])`` formulation exactly (the reorganized scatter is
algebraically the same sum).  Two backwards, chosen by what the manifold
states about its own distance: Lorentz offers ``sqdist_of_dot`` and runs
the one that lists a scalar a pair and re-gathers the other end only,
Poincaré the generic one that re-gathers both ends."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.kernels.segment import rows_for_device_plan
from hyperspace_tpu.manifolds.lorentz import minkowski_dot, minkowski_flip
from hyperspace_tpu.nn.gcn import make_manifold
from hyperspace_tpu.telemetry import registry

GAUGE = "decoder/pair_vjp_rows_per_step"


def _vjp_rows(kind, p):
    """The rows the backward of ``kind`` re-gathers from ``z``: the 2P
    listings padded for the device plan, twice that where both ends of
    each listing are gathered."""
    e = rows_for_device_plan(2 * p)
    return e if kind == "lorentz" else 2 * e


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
    assert registry.snapshot()[GAUGE] == _vjp_rows(kind, p)
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
    assert registry.snapshot()[GAUGE] == _vjp_rows(kind, p)
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


@pytest.mark.parametrize("jit", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["lorentz", "poincare"])
def test_pair_sqdist_values_bitwise(kind, dtype, jit, rng):
    """The forward is ``m.sqdist(z[u], z[v])``, bit for bit: the
    structured path computes the same two steps and keeps the dot."""
    from hyperspace_tpu.nn.edge_dist import pair_sqdist

    n, p = 300, 1100
    m = make_manifold(kind, 0.8)
    z = m.random_normal(jax.random.PRNGKey(5), (n, m.ambient_dim(8)),
                        jnp.float32, std=0.5).astype(dtype)
    u, v = _pairs_with_a_hub(rng, n, p)
    c = jnp.asarray(0.8, jnp.float32)
    got = lambda z, c: pair_sqdist(z, c, u, v, kind)
    want = lambda z, c: make_manifold(kind, c).sqdist(z[u], z[v])
    if jit:
        got, want = jax.jit(got), jax.jit(want)
    a, b = got(z, c), want(z, c)
    assert a.dtype == b.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_lorentz_rows_are_a_scalar_times_the_other_end(dtype, rng):
    """What the structured backward rests on, op by op and bit for bit:
    the rows sqdist's autodiff computes for a pair are s·J·z[other end]
    with ONE scalar s a pair, the scalar map's own derivative at the
    pair's Minkowski dot — coincident ends and the hub included."""
    n, p = 300, 1100
    m = make_manifold("lorentz", 0.8)
    z = m.random_normal(jax.random.PRNGKey(6), (n, 9), jnp.float32,
                        std=0.5).astype(dtype)
    u, v = _pairs_with_a_hub(rng, n, p)
    t = jnp.asarray(rng.standard_normal(p), dtype)
    _, vjp = jax.vjp(m.sqdist, z[u], z[v])
    gu, gv = vjp(t)
    _, vjp = jax.vjp(m.sqdist_of_dot,
                     minkowski_dot(z[u], z[v], keepdims=False))
    (s,) = vjp(t)
    assert s.dtype == jnp.dtype(dtype) and s.shape == (p,)
    for rows, oth in ((gu, v), (gv, u)):
        np.testing.assert_array_equal(
            np.asarray(rows, np.float32),
            np.asarray(minkowski_flip(s[:, None] * z[oth]), np.float32))


@pytest.mark.parametrize("mode", ["xla", "interpret"])
@pytest.mark.parametrize("kind", ["lorentz", "poincare"])
def test_pair_sqdist_learned_curvature(kind, mode, rng, monkeypatch):
    """`learn_c`: the curvature is a traced function of a parameter and
    its cotangent is plain autodiff's (summed once over the pairs, not
    twice and halved), under jit, at several curvatures."""
    from hyperspace_tpu.nn.edge_dist import pair_sqdist

    monkeypatch.setenv("HYPERSPACE_KERNELS", mode)
    n, p = 200, 700
    u, v = _pairs_with_a_hub(rng, n, p)
    t = jnp.asarray(rng.standard_normal(p), jnp.float64)
    for raw in (-1.0, 0.3, 2.0):
        m = make_manifold(kind, float(jax.nn.softplus(raw)))
        z = m.random_normal(jax.random.PRNGKey(7), (n, m.ambient_dim(6)),
                            jnp.float64, std=0.3)

        def loss(raw, sq):
            return jnp.sum(sq(jax.nn.softplus(raw)) * t)

        got = jax.jit(jax.grad(lambda r: loss(
            r, lambda c: pair_sqdist(z, c, u, v, kind))))(jnp.float64(raw))
        want = jax.jit(jax.grad(lambda r: loss(
            r, lambda c: make_manifold(kind, c).sqdist(z[u], z[v]))))(
                jnp.float64(raw))
        assert np.isfinite(float(got)) and float(got) != 0.0
        np.testing.assert_allclose(float(got), float(want), rtol=1e-9)


@pytest.mark.parametrize("dtype", ["float64", "float32"])
@pytest.mark.parametrize("kind", ["lorentz", "poincare"])
def test_pair_sqdist_coincident_ends_and_a_hub(kind, dtype, rng):
    """Every pair's two ends one node (distance 0, where the clamps
    inside the scalar map decide the derivative) and, in a second batch,
    every pair on one hub: finite, and plain autodiff's sum."""
    from hyperspace_tpu.nn.edge_dist import pair_sqdist

    n, p = 150, 600
    m = make_manifold(kind, 1.0)
    z = m.random_normal(jax.random.PRNGKey(8), (n, m.ambient_dim(8)),
                        dtype, std=0.3)
    t = jnp.asarray(rng.standard_normal(p), dtype)
    c = jnp.asarray(1.0, dtype)
    same = jnp.asarray(rng.integers(0, n, p).astype(np.int32))
    hub = jnp.full((p,), n // 3, jnp.int32)
    far = jnp.asarray(rng.integers(0, n, p).astype(np.int32))
    for u, v in ((same, same), (hub, far), (far, hub)):
        got = jax.grad(lambda zz, cc: jnp.sum(
            pair_sqdist(zz, cc, u, v, kind) * t), argnums=(0, 1))(z, c)
        want = jax.grad(lambda zz, cc: jnp.sum(
            make_manifold(kind, cc).sqdist(zz[u], zz[v]) * t),
            argnums=(0, 1))(z, c)
        tol = 1e-9 if dtype == "float64" else 2e-4
        for a, b in zip(got, want):
            assert np.all(np.isfinite(np.asarray(a)))
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=tol,
                atol=tol * float(jnp.max(jnp.abs(b))) + 1e-12)


@pytest.mark.parametrize("kind", ["lorentz", "poincare"])
def test_backward_gathers_the_rows_its_path_needs(kind, rng):
    """The lowered backward of a Lorentz call holds ONE row gather of
    ``rows_for_device_plan(2P)`` rows and none of twice that; a Poincaré
    call (the generic path) the opposite; the gauge says which ran."""
    from hyperspace_tpu.nn.edge_dist import pair_sqdist

    n, p, bf16 = 300, 1100, jnp.bfloat16
    m = make_manifold(kind, 1.0)
    d = m.ambient_dim(8)
    z = m.random_normal(jax.random.PRNGKey(9), (n, d), jnp.float32,
                        std=0.3).astype(bf16)
    u, v = _pairs_with_a_hub(rng, n, p)
    c = jnp.asarray(1.0, jnp.float32)
    registry.set_gauge(GAUGE, -1)
    text = jax.jit(jax.grad(lambda zz: jnp.sum(
        pair_sqdist(zz, c, u, v, kind).astype(jnp.float32)))).lower(
            z).as_text()
    e = rows_for_device_plan(2 * p)
    gathers = lambda rows: len(re.findall(
        rf"stablehlo\.gather.*-> tensor<{rows}x{d}xbf16>", text))
    # (the forward's pair gathers are [P, d]: only the backward's reach
    # the padded listing's length)
    want = {"lorentz": (1, 0), "poincare": (0, 1)}[kind]
    assert (gathers(e), gathers(2 * e)) == want, text
    assert registry.snapshot()[GAUGE] == _vjp_rows(kind, p)
