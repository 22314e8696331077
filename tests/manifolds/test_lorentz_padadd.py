"""The Lorentz lifts are pad+add, bitwise-equal to the concat forms.

An earlier jax's GSPMD partitioner miscompiled `concatenate` whose
operands are sharded over a subset of a multi-axis mesh's axes (the
reduced program: tests/parallel/test_node_sharded.py::
test_gspmd_concat_under_subset_constraint, which passes on the installed
jax 0.9.0), so every Lorentz time-coordinate lift/split was rewritten
as pad(+add) (manifolds/lorentz._pad_last / with_time_coordinate).
These tests pin the rewrite to the old `jnp.concatenate` forms
BITWISE on a single device — the rewrite is a partitioner dodge, never
a numerics change.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.manifolds import Lorentz, smath
from hyperspace_tpu.manifolds.lorentz import with_time_coordinate


def _bitwise(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    assert a.tobytes() == b.tobytes(), (
        f"bitwise mismatch: max abs diff {np.max(np.abs(a - b))}")


@pytest.fixture(params=[jnp.float32, jnp.float64])
def data(request):
    dt = request.param
    k = jax.random.PRNGKey(7)
    kx, kg, kv = jax.random.split(k, 3)
    x = jax.random.normal(kx, (17, 9), dt)
    g = jax.random.normal(kg, (17, 9), dt)
    v = jax.random.normal(kv, (17, 8), dt)
    return dt, x, g, v


@pytest.mark.parametrize("c", [1.0, 0.7])
def test_proj_matches_concat_form(data, c):
    dt, x, _, _ = data
    m = Lorentz(c)
    sp = x[..., 1:]
    cc = jnp.asarray(c, dt)
    t = smath.safe_sqrt(
        1.0 / smath.clamp_min(cc, smath.min_norm(dt)) + smath.sq_norm(sp))
    _bitwise(m.proj(x), jnp.concatenate([t, sp], axis=-1))


def test_with_time_coordinate_matches_concat_form(data):
    dt, x, _, _ = data
    sp = x  # any space block
    cc = jnp.asarray(0.9, dt)
    t = smath.safe_sqrt(
        1.0 / smath.clamp_min(cc, smath.min_norm(dt)) + smath.sq_norm(sp))
    _bitwise(with_time_coordinate(sp, cc),
             jnp.concatenate([t, sp], axis=-1))


def test_origin_matches_concat_form(data):
    dt, _, _, _ = data
    m = Lorentz(1.3)
    shape = (5, 9)
    o = jnp.zeros(shape, dt)
    t = jnp.ones(shape[:-1] + (1,), dt) / smath.sqrt_c(jnp.asarray(1.3, dt))
    _bitwise(m.origin(shape, dt), jnp.concatenate([t, o[..., 1:]], axis=-1))


def test_egrad2rgrad_matches_concat_form(data):
    dt, x, g, _ = data
    m = Lorentz(1.0)
    xp = m.proj(x)
    gl = jnp.concatenate([-g[..., :1], g[..., 1:]], axis=-1)
    _bitwise(m.egrad2rgrad(xp, g), m.proju(xp, gl))


def test_tangent_lift_matches_concat_form(data):
    dt, _, _, v = data
    m = Lorentz(1.0)
    _bitwise(m.tangent_from_origin_coords(v),
             jnp.concatenate([jnp.zeros_like(v[..., :1]), v], axis=-1))


def test_gcn_tangent_roundtrip_unchanged(data):
    """from_tangent0_coords routes through the pad lift — the chart
    round-trip (gcn.tangent0_coords ∘ from_tangent0_coords) stays
    exact and on-manifold."""
    from hyperspace_tpu.nn import gcn

    dt, _, _, v = data
    m = Lorentz(1.0)
    x = gcn.from_tangent0_coords(m, v)
    assert np.max(np.asarray(m.check_point(x))) < 1e-5
    old = m.expmap0(jnp.concatenate(
        [jnp.zeros_like(v[..., :1]), v], axis=-1))
    _bitwise(x, old)


def test_no_concatenate_left_in_lorentz_lifts():
    """Source-level pin: manifolds/lorentz.py must stay concatenate-free
    (the sharded-path rule — a re-grown concat would silently re-arm
    the GSPMD miscompile on multi-axis meshes)."""
    import ast
    import inspect

    from hyperspace_tpu.manifolds import lorentz as L

    calls = [n for n in ast.walk(ast.parse(inspect.getsource(L)))
             if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Attribute)
             and n.func.attr == "concatenate"]
    assert not calls, f"concatenate re-grew at lines {[c.lineno for c in calls]}"
