"""Shared support for the Pallas TPU kernel layer (SURVEY.md §2 N1-N8).

Three concerns live here:

1. **Dispatch** — every public kernel has a pure-JAX twin (the oracle).
   ``mode()`` decides per-call which implementation runs:
   ``pallas`` on a TPU backend, ``xla`` (the twin) elsewhere, overridable
   with ``HYPERSPACE_KERNELS={auto,pallas,interpret,xla}``.  ``interpret``
   runs the Pallas kernel through the interpreter on CPU — how the parity
   tests execute kernels without hardware (SURVEY.md §4.4).

2. **Mosaic-safe math** (``k*`` functions) — the kernels may only rely on
   transcendentals the Mosaic TPU compiler lowers robustly (exp/log/sqrt/
   tanh), so artanh/asinh/arcosh are spelled out in log/sqrt form with the
   same clamping policy as :mod:`hyperspace_tpu.manifolds.smath`.

3. **Tile padding** — TPU tiles are (8,128) f32; helpers pad row and lane
   dimensions with zeros.  All hyperbolic formulas used in the kernels are
   sums of products over the feature axis, so zero lanes are exact no-ops;
   zero rows are valid points (the origin) and get sliced off after.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

_SUBLANE = 8
_LANE = 128

# Epsilon policy mirrors smath (kernels run f32 compute).
EPS_F32 = 1e-7
MIN_NORM_F32 = 1e-12
BALL_EPS_F32 = 4e-3
ARTANH_EPS_F32 = 3e-7


def tpu_compiler_params(**kw):
    """``pltpu.CompilerParams(**kw)`` (``dimension_semantics`` etc.) —
    imported here so kernel modules need no pallas-tpu import of their
    own."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kw)


def mode() -> str:
    """Resolve the kernel implementation for the current call site."""
    m = os.environ.get("HYPERSPACE_KERNELS", "auto")
    if m not in ("auto", "pallas", "interpret", "xla"):
        raise ValueError(f"HYPERSPACE_KERNELS={m!r} (want auto|pallas|interpret|xla)")
    if m == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return m


def interpret_flag(m: str) -> bool:
    return m == "interpret"


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def pad_axis(x: jax.Array, axis: int, to: int) -> jax.Array:
    """Zero-pad ``axis`` of ``x`` up to a multiple of ``to``."""
    n = x.shape[axis]
    pad = round_up(n, to) - n
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def pad_rows_lanes(x: jax.Array, rows_to: int = _SUBLANE, lanes_to: int = _LANE) -> jax.Array:
    return pad_axis(pad_axis(x, -1, lanes_to), -2, rows_to)


VMEM_BUDGET = 4 * 1024 * 1024  # per-kernel working-set target (VMEM is ~16 MB)


def row_block(n_rows: int, dp: int = _LANE, n_bufs: int = 2, cap: int = 512) -> int:
    """Pick a row-block size under a VMEM budget.

    ``dp`` is the padded lane count and ``n_bufs`` the number of row-shaped
    VMEM buffers the kernel holds (inputs + output); the block shrinks for
    wide features so n_bufs × bn × dp × 4 B stays within VMEM_BUDGET
    (Pallas double-buffers blocks, hence the conservative target).
    """
    by_budget = VMEM_BUDGET // (4 * dp * max(n_bufs, 1))
    bn = max(_SUBLANE, (by_budget // _SUBLANE) * _SUBLANE)
    return min(round_up(n_rows, _SUBLANE), cap, bn)


def flatten_batch(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    """[..., d] -> ([N, d], leading shape)."""
    lead = x.shape[:-1]
    return x.reshape((-1, x.shape[-1])), lead


def c_smem(c, dtype=jnp.float32) -> jax.Array:
    """Scalar curvature as the (1, 1) array SMEM wants (guide §Pitfall 8)."""
    return jnp.asarray(c, dtype).reshape(1, 1)


def dotT(a: jax.Array, b: jax.Array) -> jax.Array:
    """[n, k] × [m, k] → [n, m], contracting the last axis of both.

    HIGHEST precision: kernel matmuls feed arcosh/asinh-amplified quantities
    (distances, logits), where the default bf16-pass matmul costs ~1e-2
    absolute.  Also the rank-1 broadcast idiom: ``dotT(ones, col)`` turns a
    per-column [m, 1] quantity into [n, m] without a transpose/relayout.
    """
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


# --- Mosaic-safe transcendentals (f32 in-kernel compute) ----------------------


def kasinh(x: jax.Array) -> jax.Array:
    """asinh via logs: sign(x)·log1p(|x| + |x|²/(1+sqrt(1+x²))), Mosaic-safe.

    The log1p form is exact for small |x| and never catastrophically
    cancels; callers bound |x| via their artanh-style clamps.
    """
    ax = jnp.abs(x)
    r = ksafe_sqrt(ax * ax + 1.0)
    return jnp.sign(x) * jnp.log1p(ax + ax * ax / (1.0 + r))


def ksafe_sqrt(x: jax.Array) -> jax.Array:
    return jnp.sqrt(jnp.maximum(x, 0.0))


def ksq_norm(x: jax.Array) -> jax.Array:
    return jnp.sum(x * x, axis=-1, keepdims=True)


def ksafe_norm(x: jax.Array) -> jax.Array:
    return ksafe_sqrt(ksq_norm(x))


def kartanh(x: jax.Array) -> jax.Array:
    """artanh via logs: 0.5*(log1p(x) - log1p(-x)), clamped inside (-1, 1)."""
    x = jnp.clip(x, -1.0 + ARTANH_EPS_F32, 1.0 - ARTANH_EPS_F32)
    return 0.5 * (jnp.log1p(x) - jnp.log1p(-x))


def ktanh(x: jax.Array) -> jax.Array:
    return jnp.tanh(jnp.clip(x, -20.0, 20.0))


def karcosh1p(u: jax.Array) -> jax.Array:
    """arcosh(1+u), u >= 0: log1p(u + sqrt(u*(u+2))) (same form as smath)."""
    u = jnp.maximum(u, 0.0)
    return jnp.log1p(u + ksafe_sqrt(u * (u + 2.0)))


def ktanc(x: jax.Array) -> jax.Array:
    """tanh(x)/x, smooth at 0."""
    small = jnp.abs(x) < 1e-3
    xs = jnp.where(small, 1.0, x)
    return jnp.where(small, 1.0 - x * x / 3.0, ktanh(xs) / xs)


def kartanc(x: jax.Array) -> jax.Array:
    """artanh(x)/x, smooth at 0."""
    small = jnp.abs(x) < 1e-3
    xs = jnp.where(small, 1.0, x)
    return jnp.where(small, 1.0 + x * x / 3.0, kartanh(xs) / xs)


def klambda_x(x: jax.Array, c) -> jax.Array:
    return 2.0 / jnp.maximum(1.0 - c * ksq_norm(x), EPS_F32)


def kproj(x: jax.Array, c) -> jax.Array:
    """Clamp points into the ball of curvature -c (mirrors PoincareBall.proj)."""
    sc = ksafe_sqrt(jnp.asarray(c))
    norm = jnp.maximum(ksafe_norm(x), MIN_NORM_F32)
    max_norm = (1.0 - BALL_EPS_F32) / jnp.maximum(sc, MIN_NORM_F32)
    return jnp.where(norm > max_norm, x / norm * max_norm, x)


def kmobius_add(x: jax.Array, y: jax.Array, c) -> jax.Array:
    x2 = ksq_norm(x)
    y2 = ksq_norm(y)
    xy = jnp.sum(x * y, axis=-1, keepdims=True)
    num = (1.0 + 2.0 * c * xy + c * y2) * x + (1.0 - c * x2) * y
    den = 1.0 + 2.0 * c * xy + (c * c) * x2 * y2
    return num / jnp.maximum(den, EPS_F32)


def kgyration(u: jax.Array, v: jax.Array, w: jax.Array, c) -> jax.Array:
    u2 = ksq_norm(u)
    v2 = ksq_norm(v)
    uv = jnp.sum(u * v, axis=-1, keepdims=True)
    uw = jnp.sum(u * w, axis=-1, keepdims=True)
    vw = jnp.sum(v * w, axis=-1, keepdims=True)
    c2 = c * c
    a = -c2 * uw * v2 + c * vw + 2.0 * c2 * uv * vw
    b = -c2 * vw * u2 - c * uw
    d = 1.0 + 2.0 * c * uv + c2 * u2 * v2
    return w + 2.0 * (a * u + b * v) / jnp.maximum(d, EPS_F32)
