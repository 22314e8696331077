"""Hyperbolic NN layers (flax.linen).

Implements the layer inventory of SURVEY.md §2: the gyro-linear layer
(reference CUDA kernel N5), the fully-hyperbolic Lorentz linear layer
(HyboNet), and the tangent-space activation with curvature transfer (HGCN).

Parameterization convention [PLAN]: layer-internal manifold-valued
parameters (biases, hyperplane base points) are stored as **tangent vectors
at the origin** and mapped with ``expmap0`` in the forward pass.  The stored
parameter is Euclidean, so these layers train under any optax optimizer and
need no manifold-tag plumbing through flax; the *unconstrained-storage +
constrained-forward* pattern is the TPU-friendly equivalent of the
reference's ManifoldParameter class.  Embedding tables, by contrast, are
true on-manifold parameters driven by :mod:`hyperspace_tpu.optim` with
manifold tags.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from hyperspace_tpu.kernels.hyplinear import hyp_linear
from hyperspace_tpu.manifolds import Lorentz, PoincareBall
from hyperspace_tpu.manifolds import lorentz, smath
from hyperspace_tpu.precision import compute_matmul


class HypLinear(nn.Module):
    """Gyro-linear layer on the Poincaré ball: y = (M ⊗_c x) ⊕_c b.

    Semantics per Ganea et al. 2018 (reference kernel N5, SURVEY.md §2
    "HypLinear / gyro-linear").  Input/output are points on the ball of the
    layer's manifold.
    """

    features: int
    manifold: PoincareBall
    use_bias: bool = True
    kernel_init: Callable = nn.initializers.glorot_uniform()

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        d_in = x.shape[-1]
        kernel = self.param("kernel", self.kernel_init, (d_in, self.features), x.dtype)
        if self.use_bias:
            # bias is a tangent vector at the origin; exp0 makes it a point
            bias_t = self.param("bias", nn.initializers.zeros, (self.features,), x.dtype)
            b = self.manifold.expmap0(bias_t)
        else:
            b = jnp.zeros((self.features,), x.dtype)  # x ⊕ 0 = x exactly
        # fused matmul → Möbius rescale → ⊕ bias → proj (kernel N5)
        return hyp_linear(x, kernel, b, self.manifold.c)


class LorentzLinear(nn.Module):
    """Fully-hyperbolic linear layer on the hyperboloid (HyboNet).

    Semantics per Chen et al. ACL 2022 (SURVEY.md §2 "LorentzLinear"): the
    full ambient input (time + space coordinates) feeds an ordinary matmul
    producing the output *space* coordinates, and the output time coordinate
    is reconstructed from the hyperboloid constraint

        t = sqrt(1/c + ‖space‖²).

    No tangent-space detour — one MXU matmul plus a norm, and the output is
    on-manifold by construction (the TPU-native win of the Lorentz model).
    ``dim`` is the *manifold* dimension: output ambient shape is dim+1.
    """

    dim: int
    manifold: Lorentz
    use_bias: bool = True
    activation: Optional[Callable] = None
    kernel_init: Callable = nn.initializers.glorot_uniform()
    # mixed-precision compute dtype for the matmul ONLY (the layer's MXU
    # mass): inputs and kernel are cast to it, the product is cast back
    # to the storage dtype BEFORE the bias add and the time-coordinate
    # reconstruction — the hyperboloid constraint math (safe_sqrt of
    # 1/c + ‖space‖²) always runs full-precision.  None (default) is the
    # exact pre-policy layer (hyperspace_tpu/precision.py).
    compute_dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        d_in = x.shape[-1]
        kernel = self.param("kernel", self.kernel_init, (d_in, self.dim), x.dtype)
        h = x
        if self.activation is not None:
            h = self.activation(h)
        space = compute_matmul(h, kernel, self.compute_dtype)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros, (self.dim,), x.dtype)
            space = space + bias
        # time-coordinate reconstruction: pad+add, never concatenate
        # (manifolds/lorentz.with_time_coordinate — the sharded-path rule)
        return lorentz.with_time_coordinate(
            space, jnp.asarray(self.manifold.c, x.dtype))


class HypAct(nn.Module):
    """Tangent-space activation with curvature transfer (HGCN).

    y = exp0^{c_out}( act( log0^{c_in}(x) ) ) — Chami et al. 2019 use this
    between layers whose curvatures differ (SURVEY.md §3.2 "curvature_{l+1}
    transfer").  Works for any pair of manifolds that share a tangent space
    at the origin of the same width (ball→ball, lorentz→lorentz).
    """

    manifold_in: Any
    manifold_out: Any
    activation: Callable = nn.relu

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        # activate in the origin chart: unconstrained coordinates, so any
        # elementwise nonlinearity keeps the result a valid tangent vector
        m_in, m_out = self.manifold_in, self.manifold_out
        v = m_in.origin_coords_from_tangent(m_in.logmap0(x))
        v = self.activation(v)
        return m_out.expmap0(m_out.tangent_from_origin_coords(v))


# --- Euclidean transformer parts (models/looplm.py) ---------------------------
# The curvature-zero case: a drawn architecture runs by its own published
# equations.  Functions, not modules: the looped model stacks its weights
# [L, ...] and scans over them, which flax's per-module scopes do not fit.
# The precision policy's lanes: norms, rotary angles and the gate of the
# feed-forward in float32 (``boundary``/``accum``); the matmuls' operands
# in ``compute`` (precision.compute_matmul).


def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    """x / sqrt(mean(x²) + eps) ⊙ gain, in float32 whatever x's dtype."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float, beta_slow: float):
    """YaRN's per-pair frequencies over a rotated width ``dim`` (Peng et
    al., arXiv:2309.00071, in the transformers library's form with its
    default truncation): pairs that turn more than ``beta_fast`` times
    over ``original_max`` positions keep theta's frequency, pairs that
    turn fewer than ``beta_slow`` times take it divided by ``factor``, and
    a linear ramp blends the ones between."""
    def turns_dim(turns):
        return (dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return (1.0 / (factor * pos)) * (1.0 - keep) + (1.0 / pos) * keep


def rotary_tables(length: int, head_dim: int, theta: float, *,
                  rotary_dim: Optional[int] = None, yarn=None):
    """(cos, sin), each [length, 1, rotary_dim] float32, for positions
    0 … length-1 in the rotate-half form (the angle of lane i and of lane
    i + rotary_dim/2 is position · theta^(-2i/rotary_dim)).  ``rotary_dim``
    (default the whole head) is the width a partial rotary turns, the
    head's first lanes; ``yarn`` = (factor, original_max, beta_fast,
    beta_slow, attention_factor) takes :func:`yarn_inv_freq`'s
    frequencies and multiplies both tables by ``attention_factor``."""
    dim = head_dim if rotary_dim is None else int(rotary_dim)
    if yarn is None:
        inv = 1.0 / (theta ** (
            jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    else:
        inv = yarn_inv_freq(dim, theta, *yarn[:4])
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    if yarn is None:
        return jnp.cos(ang), jnp.sin(ang)
    return jnp.cos(ang) * yarn[4], jnp.sin(ang) * yarn[4]


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [S, H, D] rotated by its position's angles, in float32.  Tables
    narrower than the head turn its first lanes and leave the rest."""
    x = x.astype(jnp.float32)
    width = cos.shape[-1]
    if width < x.shape[-1]:
        return jnp.concatenate([apply_rotary(x[..., :width], cos, sin),
                                x[..., width:]], axis=-1)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array, matmul: Callable = jnp.matmul) -> jax.Array:
    """(silu(x W_gate) ⊙ (x W_up)) W_down; ``matmul`` is the policy's
    (``precision.Policy.matmul``: operands on the compute lane, the
    product float32), so the gate's product runs in float32."""
    return matmul(jax.nn.silu(matmul(x, w_gate)) * matmul(x, w_up), w_down)
