"""Lorentz specifics + ball↔hyperboloid isometry tests (SURVEY.md §4.1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.manifolds import (
    Lorentz,
    PoincareBall,
    ball_to_lorentz,
    lorentz_to_ball,
    minkowski_dot,
)


@pytest.fixture(params=[0.5, 1.0, 2.0])
def c(request):
    return request.param


def test_roundtrip(c):
    lor = Lorentz(c)
    x = lor.random_normal(jax.random.PRNGKey(0), (32, 7), jnp.float64)
    y = lorentz_to_ball(x, c)
    x2 = ball_to_lorentz(y, c)
    np.testing.assert_allclose(np.asarray(x2), np.asarray(x), atol=1e-9)
    # and the image is inside the ball
    assert np.all(c * np.sum(np.asarray(y) ** 2, -1) < 1.0)


def test_isometry(c):
    """Distances agree between the two models (maps are isometries)."""
    lor, ball = Lorentz(c), PoincareBall(c)
    k = jax.random.split(jax.random.PRNGKey(1), 2)
    x = lor.random_normal(k[0], (32, 7), jnp.float64)
    y = lor.random_normal(k[1], (32, 7), jnp.float64)
    d_l = np.asarray(lor.dist(x, y))
    d_b = np.asarray(ball.dist(lorentz_to_ball(x, c), lorentz_to_ball(y, c)))
    np.testing.assert_allclose(d_b, d_l, rtol=1e-8, atol=1e-10)


def test_dist_golden(c):
    """d(o, exp_o(t e₁)) = t for any radial tangent step."""
    lor = Lorentz(c)
    o = lor.origin((1, 4), jnp.float64)
    t = 1.37
    v = jnp.zeros((1, 4), jnp.float64).at[..., 1].set(t)
    y = lor.expmap(o, v)
    np.testing.assert_allclose(np.asarray(lor.dist(o, y))[0], t, rtol=1e-10)


def test_centroid_on_manifold_and_symmetric(c):
    lor = Lorentz(c)
    x = lor.random_normal(jax.random.PRNGKey(2), (8, 5, 4), jnp.float64)
    mu = lor.centroid(x)
    np.testing.assert_allclose(
        np.asarray(minkowski_dot(mu, mu, keepdims=False)), -1.0 / c, rtol=1e-9
    )
    # centroid of {y, y} is y
    y = x[:, :1]
    mu2 = lor.centroid(jnp.concatenate([y, y], axis=-2))
    np.testing.assert_allclose(np.asarray(mu2), np.asarray(y[:, 0]), atol=1e-9)


def test_egrad2rgrad_tangency(c):
    lor = Lorentz(c)
    x = lor.random_normal(jax.random.PRNGKey(3), (16, 5), jnp.float64)
    g = jax.random.normal(jax.random.PRNGKey(4), x.shape, x.dtype)
    rg = lor.egrad2rgrad(x, g)
    np.testing.assert_allclose(
        np.asarray(minkowski_dot(x, rg, keepdims=False)), 0.0, atol=1e-9
    )


@pytest.mark.parametrize("jit", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("fn", ["dist", "sqdist"])
def test_distance_split_is_the_inline_formula_bitwise(fn, dtype, jit):
    """`Lorentz.dist` / `sqdist` are the Minkowski dot followed by a
    scalar map (`dist_of_dot`, `sqdist_of_dot`: one definition, the LP
    decoder's backward differentiates the map alone).  The split is a
    refactor: values, both arguments' gradients and the curvature's are
    bit for bit those of the formula as it stood in one piece, coincident
    points (u = 0, where the clamps decide) included."""
    from hyperspace_tpu.manifolds import smath

    def inline(x, y, c):
        c = jnp.asarray(c, x.dtype)
        u = (-c * minkowski_dot(x, y) - 1.0)[..., 0]
        d = smath.arcosh1p(u) / smath.sqrt_c(c)
        return d if fn == "dist" else d ** 2

    def split(x, y, c):
        return getattr(Lorentz(c), fn)(x, y)

    kx, ky, kt = jax.random.split(jax.random.PRNGKey(11), 3)
    m = Lorentz(0.7)
    x = m.random_normal(kx, (64, 9), jnp.float32, std=0.8).astype(dtype)
    y = m.random_normal(ky, (64, 9), jnp.float32, std=0.8).astype(dtype)
    y = y.at[:16].set(x[:16])  # coincident
    t = jax.random.normal(kt, (64,), jnp.float32).astype(dtype)
    c = jnp.asarray(0.7, jnp.float32)
    outs = []
    for f in (split, inline):
        vg = jax.value_and_grad(
            lambda x, y, c: jnp.sum((f(x, y, c) * t).astype(jnp.float32)),
            argnums=(0, 1, 2))
        outs.append(jax.tree.leaves(
            ((jax.jit(f) if jit else f)(x, y, c),
             (jax.jit(vg) if jit else vg)(x, y, c))))
    for a, b in zip(*outs):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.all(np.isfinite(a.astype(np.float32)))
        assert a.tobytes() == b.tobytes()
