"""Central mixed-precision policy — the ONE place bf16 is allowed in.

The TPU roofline the bench reports assumes the MXU's bf16 path
(197 Tflops bf16 vs 99 Tflops f32 on the reference chip), but hyperbolic
workloads are exactly where naive half precision breaks: the Poincaré
conformal factor 1/(1 − c‖x‖²) and every artanh/arcosh argument lose all
their information to bf16's 8-bit mantissa near the boundary (Nickel &
Kiela 2017; Chami et al. 2019 — the failure modes telemetry/health.py
monitors).  So the policy casts *selectively*, never globally:

==================  =========================================================
field               what runs in it
==================  =========================================================
``param``           master parameters / embedding tables (optimizer state
                    included — RAdam/RSGD moments are NEVER downcast)
``compute``         dense/conv/attention matmul inputs and activations —
                    the MXU-shaped Euclidean mass of a model
``accum``           reductions: losses, means, segment sums, metric sums
``boundary``        boundary-sensitive manifold math — exp/log/proj,
                    distances, conformal factors, hyperboloid time
                    coordinates — and anything feeding artanh/arcosh
==================  =========================================================

Presets::

    f32   param=f32  compute=f32   accum=f32  boundary=f32   (the default;
          every cast helper is the IDENTITY, so behavior is bit-identical
          to a build without this module)
    bf16  param=f32  compute=bf16  accum=f32  boundary=f32

Consumers never write ``jnp.bfloat16`` themselves — they take a policy
(usually from a config's ``precision: str`` field) and use the cast
helpers.  ``scripts/check_precision_policy.py`` lints the package for
ad-hoc bf16 literals outside this module and the kernel fast paths, so
casts can't bypass the policy.

Wiring map (docs/precision.md has the full table):

- models: HVAE conv/dense stacks and HyboNet's LorentzLinear matmuls run
  in ``compute``; HGCN maps ``precision=bf16`` onto its quality-validated
  ``agg_dtype``/``decoder_dtype`` bf16 message path; embedding-table
  workloads (poincare/product) are all-boundary, so their train step is
  documented f32 under every preset.
- train: ``train/loop.make_chunked_stepper(policy=...)`` casts explicit
  batch args to ``compute`` once per scanned chunk.
- serve: ``serve/engine.QueryEngine(precision="bf16")`` scans the table
  in bf16 and rescores the merged candidates in f32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp

PRESET_NAMES = ("f32", "bf16")


@dataclasses.dataclass(frozen=True)
class Policy:
    """Dtype assignment for one run.  Immutable and hashable, so it can
    ride in frozen model configs and jit static arguments."""

    name: str
    param: Any = jnp.float32
    compute: Any = jnp.float32
    accum: Any = jnp.float32
    boundary: Any = jnp.float32

    @property
    def mixed(self) -> bool:
        """True when the compute dtype differs from f32 — the ONLY case
        any cast helper does work (the f32 preset is the identity by
        construction, which is what makes ``precision=f32`` bit-identical
        to the pre-policy code)."""
        return jnp.dtype(self.compute) != jnp.dtype(jnp.float32)

    # --- cast helpers ---------------------------------------------------------
    # All helpers are identity for non-floating arrays (ids, masks) and
    # for the f32 preset; they return the input object unchanged whenever
    # no cast is needed, so the default path adds zero ops to the graph.

    def _cast(self, x, dt):
        if not self.mixed:
            return x
        x = jnp.asarray(x) if not hasattr(x, "dtype") else x
        if (jnp.issubdtype(x.dtype, jnp.floating)
                and x.dtype != jnp.dtype(dt)):
            return x.astype(dt)
        return x

    def cast_compute(self, x):
        """Activation/matmul-input cast (→ ``compute``)."""
        return self._cast(x, self.compute)

    def cast_boundary(self, x):
        """Manifold-op input cast (→ ``boundary``, f32 in every preset):
        call this where a compute-dtype activation is about to feed
        exp/log/proj/dist or any artanh/arcosh-shaped expression."""
        return self._cast(x, self.boundary)

    def cast_accum(self, x):
        """Reduction input cast (→ ``accum``)."""
        return self._cast(x, self.accum)

    def cast_param(self, x):
        """Master-parameter cast (→ ``param``)."""
        return self._cast(x, self.param)

    def cast_compute_tree(self, tree):
        """``cast_compute`` over every floating leaf of a pytree
        (integer/bool leaves — ids, masks — pass through untouched)."""
        if not self.mixed:
            return tree
        return jax.tree_util.tree_map(self.cast_compute, tree)

    def matmul(self, x, w):
        """``x @ w`` (x [..., k], w [k, n]) with both operands on the
        ``compute`` lane and the product in ``accum`` (the MXU's float32
        accumulator kept, not rounded back to the operands' dtype); see
        :func:`lane_matmul` for the backward.  Plain ``x @ w`` for the
        f32 preset."""
        if not self.mixed:
            return x @ w
        return lane_matmul(x, w, self.compute, self.accum)

    def module_dtype(self):
        """The ``dtype=`` to hand a flax module: ``compute`` when mixed,
        ``None`` (flax's promote-inputs default) otherwise — passing an
        explicit f32 would be equivalent but None keeps the f32 preset
        textually identical to the pre-policy modules."""
        return self.compute if self.mixed else None


F32 = Policy("f32")
BF16 = Policy("bf16", compute=jnp.bfloat16)

_PRESETS = {"f32": F32, "bf16": BF16}


def get_policy(p: Union[None, str, Policy]) -> Policy:
    """Resolve ``None`` (→ f32), a preset name, or a Policy instance.

    Raises ``ValueError`` for unknown names — CLI layers turn that into
    a usage error listing the presets.
    """
    if p is None:
        return F32
    if isinstance(p, Policy):
        return p
    try:
        return _PRESETS[p]
    except (KeyError, TypeError):
        raise ValueError(
            f"unknown precision {p!r} (want one of {PRESET_NAMES})"
        ) from None


def compute_matmul(x, w, compute_dtype=None):
    """``x @ w`` on the policy's compute lane: inputs cast to
    ``compute_dtype``, the product cast back to ``x.dtype`` so whatever
    follows (bias adds, time-coordinate reconstructions — the boundary
    lane) runs full-precision.  ``None`` is the plain matmul, untouched.
    The ONE home of this pattern — layer modules (``nn/layers.py``,
    ``nn/attention.py``) call it instead of hand-rolling the casts, so
    the contract can't drift between sites.

    :func:`lane_matmul` is the other contract, not a second home of this
    one: its product stays in the accumulator's float32 (here it is
    rounded to ``compute_dtype`` by the matmul and cast back), and its
    backward is written out so that a float32 master weight is cast at
    its use and its gradient never rounded.  This one's callers hold
    flax parameters whose gradients autodiff may round as it likes, and
    their steps' lowered programs are pinned by sha256 (PERF.md), which
    an ``accum`` argument here would move."""
    if compute_dtype is None:
        return x @ w
    return (x.astype(compute_dtype) @ w.astype(compute_dtype)).astype(
        x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def lane_matmul(x, w, compute, accum):
    """``x @ w`` on the ``compute`` lane with an ``accum`` product, and a
    backward that stays on the lane too: the cotangent is cast to
    ``compute`` once, both transposed products accumulate in ``accum``,
    and the weight's gradient leaves in the weight's OWN dtype.  So a
    float32 master weight is cast at its use (no second copy of the
    weights is held), its gradient is never rounded to ``compute``, and a
    weight used several times in one step (models/looplm.py) sums its
    gradient in float32."""
    return jnp.matmul(x.astype(compute), w.astype(compute),
                      preferred_element_type=accum)


def _lane_matmul_fwd(x, w, compute, accum):
    xc = x.astype(compute)
    y = jnp.matmul(xc, w.astype(compute), preferred_element_type=accum)
    # x's dtype rides as an empty array (a dtype is no valid residual)
    return y, (xc, w, jnp.zeros((0,), x.dtype))


def _lane_matmul_bwd(compute, accum, res, g):
    xc, w, x_like = res
    gc = g.astype(compute)
    dx = jnp.matmul(gc, w.astype(compute).T, preferred_element_type=accum)
    k = xc.shape[-1]
    dw = jnp.matmul(xc.reshape(-1, k).T, gc.reshape(-1, gc.shape[-1]),
                    preferred_element_type=accum)
    return dx.astype(x_like.dtype), dw.astype(w.dtype)


lane_matmul.defvjp(_lane_matmul_fwd, _lane_matmul_bwd)


def parse_dtype(name: Union[str, Any, None], default: Any = None):
    """Map a CLI dtype string to the jnp dtype — the one sanctioned path
    from a flag like ``--agg-dtype bfloat16`` to an actual bf16 dtype
    (keeps ``jnp.bfloat16`` literals out of flag-parsing code, per the
    precision-policy lint)."""
    if name is None:
        return default
    if not isinstance(name, str):
        return name  # already a dtype
    try:
        return jnp.dtype(name)
    except TypeError:
        raise ValueError(f"unknown dtype name {name!r}") from None
