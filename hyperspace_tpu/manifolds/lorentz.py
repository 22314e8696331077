"""Lorentz (hyperboloid) model of curvature -c (c > 0).

Math follows Nickel & Kiela 2018 and Law et al. 2019 (SURVEY.md §2).  Points
live on { x ∈ R^{d+1} : ⟨x,x⟩_L = -1/c, x_0 > 0 } with the Minkowski bilinear
form ⟨x,y⟩_L = -x_0 y_0 + Σ_{i≥1} x_i y_i.  The hyperboloid is the preferred
internal representation on TPU: its ops are dominated by dot products (MXU
friendly) and it avoids the Poincaré boundary, which matters in f32/bf16
(SURVEY.md §7 "hard parts #1": prefer Lorentz internally where allowed).

Storage convention: the ambient dimension is d+1 for a d-dimensional
manifold; index 0 is the time coordinate.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from hyperspace_tpu.manifolds import smath
from hyperspace_tpu.manifolds.base import Manifold


def minkowski_dot(x: jax.Array, y: jax.Array, keepdims: bool = True) -> jax.Array:
    """⟨x, y⟩_L over the last axis."""
    res = jnp.sum(x[..., 1:] * y[..., 1:], axis=-1, keepdims=True) - x[..., :1] * y[..., :1]
    return res if keepdims else res[..., 0]


def minkowski_flip(x: jax.Array) -> jax.Array:
    """J·x, J = diag(-1, 1, …, 1): ⟨x, y⟩_L = x·(J·y), so J·y is the
    gradient of the Minkowski dot in its other argument."""
    # x - 2·pad(x₀): lane 0 is x₀ - 2x₀ = -x₀ (Sterbenz: exact),
    # space lanes subtract an exact 0 — bitwise the concat form
    return x - 2.0 * _pad_last(x[..., :1], 0, x.shape[-1] - 1)


def _pad_last(x: jax.Array, lo: int, hi: int) -> jax.Array:
    """Zero-pad the last axis by (lo, hi) — the time-coordinate
    assembly primitive.  Every Lorentz lift/split used to be a
    ``jnp.concatenate``; an earlier jax's GSPMD partitioner miscompiled
    `concatenate` whose operands are sharded over a subset of a
    multi-axis mesh, so the lifts were rewritten as pad(+add).  The
    installed jax (0.9.0) partitions that concatenate correctly
    (tests/parallel/test_node_sharded.py::
    test_gspmd_concat_under_subset_constraint passes); the pad+add form
    stays until a benchmark cell has compared the two on the chip —
    it is on the compiled hot path of every Lorentz model.
    Bitwise-equal to the concat form (x + 0.0 and x - 0.0 are exact),
    except that a -0.0 operand landing on a zero-padded lane comes out
    +0.0."""
    cfg = [(0, 0)] * (x.ndim - 1) + [(lo, hi)]
    return jnp.pad(x, cfg)


def with_time_coordinate(space: jax.Array, c) -> jax.Array:
    """Hyperboloid point from space coordinates: fix the time lane
    t = sqrt(1/c + ‖space‖²) and assemble by pad+add (the ONE home of
    the reconstruction — LorentzLinear and the attention heads route
    through it, so no Lorentz lift ever re-grows a `concatenate`)."""
    c = jnp.asarray(c, space.dtype)
    t = smath.safe_sqrt(
        1.0 / smath.clamp_min(c, smath.min_norm(space.dtype))
        + smath.sq_norm(space))
    return _pad_last(t, 0, space.shape[-1]) + _pad_last(space, 1, 0)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Lorentz(Manifold):
    c: Any = 1.0
    name = "lorentz"

    def tree_flatten(self):
        return (self.c,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def _c(self, dtype) -> jax.Array:
        return jnp.asarray(self.c, dtype)

    def ambient_dim(self, dim: int) -> int:
        return dim + 1

    # --- constraint / projections --------------------------------------------

    def proj(self, x: jax.Array) -> jax.Array:
        """Fix the time coordinate from the space coordinates."""
        return with_time_coordinate(x[..., 1:], self._c(x.dtype))

    def proju(self, x: jax.Array, u: jax.Array) -> jax.Array:
        """Tangent projection: u + c ⟨x,u⟩_L x (⟨x,x⟩_L = -1/c)."""
        c = self._c(x.dtype)
        return u + c * minkowski_dot(x, u) * x

    def check_point(self, x: jax.Array) -> jax.Array:
        # Relative residual: hyperboloid coordinates grow like e^dist, so the
        # raw ⟨x,x⟩_L + 1/c residual scales with ‖x‖² and must be normalized.
        c = self._c(x.dtype)
        scale = 1.0 / c + smath.sq_norm(x, keepdims=False)
        return jnp.abs(minkowski_dot(x, x, keepdims=False) + 1.0 / c) / scale

    def health_stats(self, x: jax.Array) -> dict:
        """Constraint-drift indicators (telemetry/health.py samples these).

        The hyperboloid's blow-up mode is ⟨x,x⟩_L drifting off −1/c
        under low-precision accumulation, which amplifies gradients
        through every arcosh/dist (Chami et al. 2019); reports the
        max/mean RELATIVE residual (``check_point``'s normalization —
        coordinates grow like e^dist, so the raw residual would scale
        with ‖x‖²) plus the max time coordinate √c·x₀ = cosh(√c·dist0),
        the cheap proxy for how far out the sheet the batch reaches.
        """
        c = self._c(x.dtype)
        v = self.check_point(x)
        return {"violation_max": jnp.max(v), "violation_mean": jnp.mean(v),
                "time_coord_max": jnp.max(smath.sqrt_c(c) * x[..., 0])}

    # --- distance -------------------------------------------------------------

    # The distance is a scalar map of the Minkowski dot alone, every clamp
    # inside it.  Whoever needs the derivative's structure reads it off
    # that: ∂sqdist/∂x = s·J·y with s = ∂sqdist_of_dot/∂ip one scalar a
    # pair (nn/edge_dist.py asks for `sqdist_of_dot` by name).

    def dist_of_dot(self, ip: jax.Array) -> jax.Array:
        """dist(x, y) from ip = ⟨x,y⟩_L: u = -c·ip - 1 ≥ 0;
        dist = arcosh(1+u)/√c (stable form)."""
        c = self._c(ip.dtype)
        return smath.arcosh1p(-c * ip - 1.0) / smath.sqrt_c(c)

    def sqdist_of_dot(self, ip: jax.Array) -> jax.Array:
        return self.dist_of_dot(ip) ** 2

    def dist(self, x: jax.Array, y: jax.Array) -> jax.Array:
        return self.dist_of_dot(minkowski_dot(x, y, keepdims=False))

    def sqdist(self, x: jax.Array, y: jax.Array) -> jax.Array:
        return self.sqdist_of_dot(minkowski_dot(x, y, keepdims=False))

    # --- exp / log ------------------------------------------------------------

    def expmap(self, x: jax.Array, v: jax.Array) -> jax.Array:
        c = self._c(x.dtype)
        sc = smath.sqrt_c(c)
        vn = smath.safe_sqrt(smath.clamp_min(minkowski_dot(v, v), 0.0))
        t = sc * vn
        # sinh(t)/(√c‖v‖_L) = sinh(t)/t = sinhc(t), smooth at v = 0.
        return self.proj(smath.safe_cosh(t) * x + smath.sinhc(t) * v)

    def logmap(self, x: jax.Array, y: jax.Array) -> jax.Array:
        c = self._c(x.dtype)
        # v = d(x,y) * (y + c⟨x,y⟩_L x) / ‖·‖_L ; smooth form via u-parameterization.
        cxy = minkowski_dot(x, y)
        w = y + c * cxy * x  # tangent direction, ⟨x,w⟩_L = 0
        wn = smath.safe_sqrt(smath.clamp_min(minkowski_dot(w, w), 0.0))
        d = self.dist(x, y)[..., None]
        return d * w / smath.clamp_min(wn, smath.min_norm(x.dtype))

    def origin(self, shape, dtype=jnp.float32) -> jax.Array:
        c = self._c(dtype)
        t = jnp.ones(shape[:-1] + (1,), dtype) / smath.sqrt_c(c)
        return _pad_last(t, 0, shape[-1] - 1)

    # --- transport / metric ---------------------------------------------------

    def inner(self, x: jax.Array, u: jax.Array, v: jax.Array, keepdims: bool = False) -> jax.Array:
        return minkowski_dot(u, v, keepdims=keepdims)

    def ptransp(self, x: jax.Array, y: jax.Array, v: jax.Array) -> jax.Array:
        """P_{x→y}(v) = v + c⟨y,v⟩_L / (1 - c⟨x,y⟩_L) (x + y)  (kernel N4)."""
        c = self._c(x.dtype)
        num = c * minkowski_dot(y, v)
        den = smath.clamp_min(1.0 - c * minkowski_dot(x, y), smath.eps_for(x.dtype))
        return v + num / den * (x + y)

    def egrad2rgrad(self, x: jax.Array, g: jax.Array) -> jax.Array:
        """Flip the time component (Minkowski metric inverse), then proju."""
        return self.proju(x, minkowski_flip(g))

    def retr(self, x: jax.Array, v: jax.Array) -> jax.Array:
        return self.proj(x + v)

    def logdetexp(self, x: jax.Array, y: jax.Array) -> jax.Array:
        """log |det d exp_x| at log_x(y) (orthonormal coords → Riemannian
        volume): (d−1)·log(sinh(√c r)/(√c r)), r = dist (Nagano et al. 2019).
        """
        c = self._c(x.dtype)
        d = x.shape[-1] - 1  # manifold dim; ambient is d+1
        r = self.dist(x, y)
        return (d - 1) * jnp.log(smath.clamp_min(
            smath.sinhc(smath.sqrt_c(c) * r), smath.eps_for(x.dtype)))

    def logdetexp_from_coords(self, v: jax.Array) -> jax.Array:
        c = self._c(v.dtype)
        r = smath.safe_norm(v, keepdims=False)  # coords are the space part
        return (v.shape[-1] - 1) * jnp.log(smath.clamp_min(
            smath.sinhc(smath.sqrt_c(c) * r), smath.eps_for(v.dtype)))

    # --- origin coordinate chart ---------------------------------------------
    # Tangents at the origin have time coordinate 0 and carry the standard
    # Euclidean metric on the space part, so the chart is pad/strip time.

    def coord_dim(self, ambient_dim: int) -> int:
        return ambient_dim - 1

    def tangent_from_origin_coords(self, v: jax.Array) -> jax.Array:
        return _pad_last(v, 1, 0)

    def origin_coords_from_tangent(self, u: jax.Array) -> jax.Array:
        return u[..., 1:]

    # --- aggregation (used by HGCN / attention on the hyperboloid) ------------

    def centroid(self, x: jax.Array, w: jax.Array | None = None) -> jax.Array:
        """Lorentz centroid (Law et al. 2019): normalize the weighted sum.

        x: [..., n, d+1]; w: [..., n] (uniform if None).
        μ = s / (√c · √(-⟨s,s⟩_L)) with s = Σ w_i x_i.
        """
        c = self._c(x.dtype)
        if w is None:
            s = jnp.sum(x, axis=-2)
        else:
            s = jnp.sum(w[..., None] * x, axis=-2)
        nrm = smath.safe_sqrt(smath.clamp_min(-minkowski_dot(s, s), smath.eps_for(x.dtype)))
        return s / (smath.sqrt_c(c) * nrm)

