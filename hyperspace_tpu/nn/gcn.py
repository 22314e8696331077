"""Hyperbolic graph convolution (HGCN, Chami et al. NeurIPS 2019).

SURVEY.md §2 "HGC conv layer" / §3.2: each layer is

    linear in the tangent space at the origin  →  attention-weighted
    neighbor aggregation (masked segment ops)  →  activation  →
    expmap back at the *next* layer's curvature.

TPU-first design decisions [PLAN]:

- All dense work happens in **origin-tangent coordinates**: one big [N, d]
  matmul on the MXU, no per-node exp/log in the inner loop.  (The reference
  family computes aggregation in the tangent space at each node x_i; at the
  origin the math is identical up to parallel transport and is one fused
  matmul instead of N small ones — the standard TPU/XLA formulation.)
- Aggregation over the padded edge list is masked ``segment_sum`` /
  segment-softmax with a static ``num_segments`` — no ragged ops
  (SURVEY.md §7 hard-part #3).
- Per-layer curvature can be **learned** (softplus-parameterized), matching
  the trainable-curvature option of the reference family.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from hyperspace_tpu.manifolds import Lorentz, PoincareBall
from hyperspace_tpu.nn.scatter import sym_segment_aggregate


# --- segment ops (shared with any graph aggregation) --------------------------


def segment_softmax(
    logits: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    mask: Optional[jax.Array] = None,
    indices_are_sorted: bool = False,
) -> jax.Array:
    """Softmax of ``logits`` within each segment; masked entries get 0.

    Max-subtracted for stability; safe for empty segments.  Pass
    ``indices_are_sorted=True`` for receiver-sorted edge lists (the
    ``data.graphs.prepare`` layout) to take XLA's sorted-scatter fast path.
    """
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    seg_max = jax.ops.segment_max(logits, segment_ids, num_segments,
                                  indices_are_sorted=indices_are_sorted)
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
    ex = jnp.exp(logits - seg_max[segment_ids])
    if mask is not None:
        ex = jnp.where(mask, ex, 0.0)
    denom = jax.ops.segment_sum(ex, segment_ids, num_segments,
                                indices_are_sorted=indices_are_sorted)
    return ex / jnp.maximum(denom[segment_ids], 1e-15)


# --- attention logits ---------------------------------------------------------


ATT_LOGIT_BOUND = 30.0


def bounded_att_logits(pre: jax.Array, negative_slope: float = 0.2):
    """leaky_relu + smooth ±30 squash: the TPU-first softmax precondition.

    The textbook segment softmax needs a per-receiver max shift for exp
    safety — on the padded-edge-list layout that costs a CSR max pass,
    an [E] gather of the maxima, and their backward bookkeeping, every
    layer (measured 0.083 s/layer fwd+bwd at arxiv scale, the single
    biggest attention overhead — docs/benchmarks.md r04).  Squashing the
    logits through ``B·tanh(·/B)`` with B=30 bounds them so ``exp`` is
    exact-range-safe in f32 AND bf16 by construction (e^±30 ≈ 1e±13),
    deleting the max machinery: the whole weight computation becomes one
    XLA-fused elementwise pass.  Unlike a hard clip the squash keeps a
    nonzero gradient everywhere (1 − tanh² ≈ 1 for |x| < 10; real logits
    live well inside that), and it doubles as a logit-explosion guard —
    the r03 attention collapse study motivated exactly this kind of
    bounding.  All attention paths (planned, fallback, node-sharded)
    share this helper so their outputs stay equivalence-testable.
    """
    lm = nn.leaky_relu(pre, negative_slope)
    return ATT_LOGIT_BOUND * jnp.tanh(lm / ATT_LOGIT_BOUND)


# --- tangent coordinate helpers ----------------------------------------------


def tangent0_coords(manifold, x: jax.Array) -> jax.Array:
    """Origin-tangent coordinates of logmap0(x) as a d-vector.

    On the hyperboloid, origin-tangent vectors have time coordinate 0, so
    the space part is a faithful coordinate chart; on the ball the tangent
    space at 0 is just R^d.
    """
    v = manifold.logmap0(x)
    if isinstance(manifold, Lorentz):
        return v[..., 1:]
    return v


def from_tangent0_coords(manifold, v: jax.Array) -> jax.Array:
    """Inverse of :func:`tangent0_coords` followed by expmap0."""
    if isinstance(manifold, Lorentz):
        # zero-pad time-coordinate lift — pad, not concatenate (the
        # sharded-path rule; see manifolds/lorentz._pad_last)
        v = manifold.tangent_from_origin_coords(v)
    return manifold.expmap0(v)


def make_manifold(kind: str, c) -> Any:
    if kind == "lorentz":
        return Lorentz(c)
    if kind == "poincare":
        return PoincareBall(c)
    if kind == "euclidean":
        # flat control (c is ignored): the same HGCConv becomes a plain
        # GCN — tangent0 charts are identities — giving the
        # hyperbolic-vs-Euclidean quality comparison a shared codepath
        from hyperspace_tpu.manifolds import Euclidean

        return Euclidean()
    raise ValueError(f"unknown manifold kind {kind!r}")


class HGCConv(nn.Module):
    """One hyperbolic graph-conv layer.

    Input: points on ``(kind, c_in)``; output: points on ``(kind, c_out)``
    — the curvature transfer happens by activating in the shared origin
    tangent chart and exp-mapping at the output curvature (SURVEY.md §3.2
    "curvature_{l+1} transfer").  When ``learn_c`` is set, ``c_out`` is a
    per-layer learned parameter (softplus of a free scalar, init at the
    given value).
    """

    features: int  # manifold dimension of the output
    kind: str = "lorentz"
    c_in: float = 1.0
    c_out: float = 1.0
    learn_c: bool = False
    use_att: bool = False
    use_bias: bool = True
    activation: Callable = nn.relu
    dropout_rate: float = 0.0
    kernel_init: Callable = nn.initializers.glorot_uniform()
    # dtype for the gathered edge messages only (the aggregation kernel
    # accumulates in f32 regardless) — jnp.bfloat16 halves the dominant
    # HBM traffic of the layer at ~bf16-matmul-level quality cost; None
    # keeps the input dtype
    agg_dtype: Optional[Any] = None

    @nn.compact
    def __call__(
        self,
        x: jax.Array,  # [N, ambient_in] points
        g,             # data.graphs.DeviceGraph (x field unused here)
        *,
        deterministic: bool = True,
    ) -> tuple[jax.Array, Any]:
        m_in = make_manifold(self.kind, self.c_in)
        if self.learn_c:
            import numpy as np

            init = float(np.log(np.expm1(self.c_out)))
            c_raw = self.param("c_raw", nn.initializers.constant(init), ())
            c_out = nn.softplus(c_raw)
        else:
            c_out = self.c_out
        m_out = make_manifold(self.kind, c_out)

        # three scopes, the layer's parts as a device profile shows them
        # (docs/observability.md): they are HLO metadata and cost nothing
        with jax.named_scope("linear"):
            v = tangent0_coords(m_in, x)  # [N, d_in]
            kernel = self.param("kernel", self.kernel_init, (v.shape[-1], self.features), v.dtype)
            h = v @ kernel  # the MXU matmul
            if self.use_bias:
                h = h + self.param("bias", nn.initializers.zeros, (self.features,), v.dtype)
            if self.dropout_rate > 0.0:
                h = nn.Dropout(self.dropout_rate)(h, deterministic=deterministic)
            alpha = None
            if self.use_att:
                # GAT-style additive attention in the tangent chart.
                a_s = self.param("att_src", self.kernel_init, (self.features, 1), h.dtype)
                a_r = self.param("att_dst", self.kernel_init, (self.features, 1), h.dtype)
                alpha = (h @ a_s)[:, 0], (h @ a_r)[:, 0]
        # jax carries the scope of a custom_vjp's call into its backward
        # rule, so the rules of nn/scatter.py open none of their own
        with jax.named_scope("aggregate"):
            agg = _aggregate(h, alpha, g, self.agg_dtype).astype(h.dtype)
        with jax.named_scope("act"):
            out = from_tangent0_coords(m_out, self.activation(agg))
        return out, m_out


def _aggregate(h: jax.Array, alpha, g, agg_dtype) -> jax.Array:
    """``[N, F]`` neighbourhood aggregate of the tangent features ``h``
    over ``g``: attention-weighted when ``alpha`` = (sender scores,
    receiver scores) is given, else the degree mean."""
    n = h.shape[0]
    # node-sharded graphs (parallel/node_shard.py) carry their own
    # per-shard edge lists + precomputed mean weights: aggregation is
    # a shard_map (all-gather + local block-CSR) and the rest of the
    # layer is ordinary row-wise math that GSPMD keeps node-sharded
    if hasattr(g, "w_fwd"):
        from hyperspace_tpu.parallel.node_shard import (
            node_sharded_aggregate,
            node_sharded_att_aggregate,
        )

        if alpha is not None:
            # receiver partitioning keeps the segment softmax
            # shard-local; autodiff collectives carry the backward
            return node_sharded_att_aggregate(h, *alpha, g, agg_dtype)
        return node_sharded_aggregate(h, g, agg_dtype)

    senders, receivers, edge_mask = g.senders, g.receivers, g.edge_mask
    sorted_fast = g.rev_perm is not None
    h_in = h if agg_dtype is None else h.astype(agg_dtype)
    w_static = False
    if alpha is not None:
        alpha_s, alpha_r = alpha
        if sorted_fast and g.plan is not None:
            # fused planned path (nn/scatter.att_partial_planned):
            # the sender pick rides the message gather as an extra
            # feature column (ONE random [E] gather/layer), bounded-
            # logit softmax needs no max pass, num/den are one CSR
            # pass, the receiver-side pick is a walk of the same CSR
            # plan, and the backward re-uses saved residual rows instead
            # of re-gathering and permutes its two per-edge scalars with
            # one key-sort.  (What an [E]-length pass costs on v5e
            # depends on its form — a 1-D scalar gather 7–10 ns an
            # edge, a row gather from fast memory under 2, a sort 1.6,
            # a CSR walk 0.7: PERF.md §6, PRs 27 and 29.)  On
            # well-clustered graphs the clustered edges drop out of
            # the [E] stream entirely:
            # their logits, weights, aggregation, and whole backward
            # run in-tile from VMEM-resident blocks
            # (nn/scatter.cluster_att_partial), and only the
            # straggler subset pays the planned passes.  The two
            # [N, F+1] (num | den) partials add and divide ONCE.
            from hyperspace_tpu.nn.scatter import (
                att_aggregate_planned,
                att_combine,
                att_partial_planned,
                cluster_att_partial,
            )

            cl = g.cluster
            if cl is not None and cl.att_ok:
                nd = cluster_att_partial(h_in, alpha_s, alpha_r, cl, n, 0.2)
                nd = nd + att_partial_planned(
                    h, alpha_s, alpha_r, cl.s_send, cl.s_recv,
                    cl.s_rev_local, cl.s_mask, cl.s_plan, n, agg_dtype, 0.2)
                return att_combine(nd, h.dtype)
            return att_aggregate_planned(
                h, alpha_s, alpha_r, senders, receivers,
                g.rev_perm, edge_mask, g.plan, n, agg_dtype, 0.2)
        logits = bounded_att_logits(alpha_s[senders] + alpha_r[receivers])
        w = segment_softmax(logits, receivers, n, mask=edge_mask,
                            indices_are_sorted=sorted_fast)
    elif g.cluster is not None:
        # cluster-pair SpMM kernel (kernels/cluster.py): block-dense
        # edges aggregate as two one-hot MXU matmuls over VMEM tiles
        # (no [E, F] message round-trip); stragglers keep the CSR
        # path; the symmetric backward runs the same two-path program
        from hyperspace_tpu.nn.scatter import cluster_sym_aggregate

        return cluster_sym_aggregate(h_in, g.cluster, n)
    else:
        # mean aggregation: 1/deg; degree is static per graph, so prefer
        # the precomputed g.deg over a per-step segment count
        ones = edge_mask.astype(h.dtype)
        if g.deg is not None:
            deg = g.deg.astype(h.dtype)
        else:
            deg = jax.ops.segment_sum(ones, receivers, n,
                                      indices_are_sorted=sorted_fast)
        w = ones / jnp.maximum(deg[receivers], 1.0)
        w_static = True
    w_in = w if agg_dtype is None else w.astype(agg_dtype)
    if sorted_fast:
        # receiver-sorted scatter in forward AND backward (nn/scatter.py)
        pb, pc, pf = g.plan if g.plan is not None else (None, None, None)
        return sym_segment_aggregate(h_in, w_in, senders, receivers,
                                     g.rev_perm, pb, pc, pf, n, not w_static)
    msgs = w_in[:, None] * h_in[senders]
    return jax.ops.segment_sum(
        msgs.astype(jnp.promote_types(msgs.dtype, jnp.float32)),
        receivers, n)
