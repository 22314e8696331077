"""Sorted symmetric segment aggregation — the TPU answer to irregular
graph scatter (SURVEY.md §7 hard-part #3).

Two pieces stack here, both exploiting the receiver-sorted edge layout
guaranteed by ``data.graphs.prepare``:

1. **Sorted both ways.** The forward aggregation

       out[r] = Σ_e  w_e · h[senders_e]        (receivers sorted ascending)

   scatters by receiver — sorted.  Autodiff's transpose scatters by
   *sender*, unsorted in this layout.  For a **symmetric** edge list
   (every (u, v) stored with its reverse (v, u)) there is an involutive
   permutation π with senders = receivers∘π; re-indexing the VJP sum
   e → π(e) turns the sender-scatter into another receiver-scatter:

       dh[i] = Σ_e w_e ḡ[r_e] δ(s_e = i) = Σ_e w_{π(e)} ḡ[s_e] δ(r_e = i)

   i.e. ``dh = segment_sum(w[π] · ḡ[senders], receivers)`` — sorted
   again.  Only the scalar weights get permuted; the [E, D] tensors
   never do.  Padding edges carry w = 0 and map to themselves under π
   (both arranged by ``prepare``), keeping π a bijection.  The
   permutation has one spelling, :func:`involute`: a key-sort by π,
   not a gather (below).

2. **Scatter as matmul.** With a CSR work-item plan (also built by
   ``prepare``), each sorted segment-sum dispatches to the block-CSR
   one-hot-matmul Pallas kernel
   (:func:`hyperspace_tpu.kernels.segment.csr_segment_sum`) instead of
   XLA's serialized scatter — ~2.4× at ogbn-arxiv scale on v5e, in both
   the forward and the re-indexed backward pass.

3. **No per-edge scalar through an XLA gather.**  On v5e a 1-D gather
   costs 7–10 ns an element whatever its table's size (1.57 M edges:
   11–15 ms), where a row gather from a table in fast memory costs
   1.6–1.9 ns a whole row.  The two kinds of scalar move the attention
   path needs have cheaper forms, both exact: the involution is the
   payload of a sort by π (1.6 ns an edge for two payloads), and the
   receiver-side pick ``alpha_r[receivers]`` happens inside the block-CSR
   walk (:func:`hyperspace_tpu.kernels.segment.csr_segment_expand_1d`,
   0.7 ns an edge).  Measured: PERF.md §6, PR 29
   (``scripts/sweep_att_edge_moves.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from hyperspace_tpu.kernels.segment import (
    csr_segment_expand_1d,
    csr_segment_sum,
)


def involute(rev_perm: jax.Array, *xs: jax.Array):
    """``x[rev_perm]`` of each ``x`` for the edge involution π (a
    bijection of ``[0, E)``; padding edges map to themselves), as ONE
    key-sort that carries every ``x`` as a payload: position k of a
    payload sorted by π holds ``x[e]`` with ``π(e) = k``, which is
    ``x[π(k)]`` because π is its own inverse.  The same values in the
    same order, bit for bit, no arithmetic; the keys are distinct, so
    the sort need not be stable (a stable one carries an iota along).
    Returns one array for one ``x``, else a tuple.

    One path for every size: on v5e the sort is ahead of XLA's gather
    from 13 k edges (0.016 against 0.11 ms) to 1.57 M (1.7–1.8 against
    13.6–15.2 ms; both payloads in one sort 2.5), and telling the gather
    that its indices are in bounds and unique changes nothing (PERF.md
    §6, PR 29).  Payloads that share π go into ONE call: XLA merges
    sorts with a common key by itself, and the merged sort loses the
    ``op_name`` that the benchmark's scopes read."""
    out = jax.lax.sort((rev_perm, *xs), num_keys=1, is_stable=False)[1:]
    return out[0] if len(xs) == 1 else out


def _sorted_segsum(vals, receivers, pb, pc, pf, num_segments):
    if pb is not None:
        return csr_segment_sum(vals, receivers, (pb, pc, pf), num_segments)
    # match the kernel's accumulate-in-≥f32 contract on the XLA fallback:
    # scatter-add in the message dtype would sum thousands of bf16 terms
    # on hub nodes (promote_types keeps f64 accumulation exact under x64)
    acc_dt = jnp.promote_types(vals.dtype, jnp.float32)
    acc = jax.ops.segment_sum(vals.astype(acc_dt), receivers,
                              num_segments, indices_are_sorted=True)
    return acc.astype(vals.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def sym_segment_aggregate(
    h: jax.Array,          # [N, D] node values
    w: jax.Array,          # [E] edge weights (0 on padding edges)
    senders: jax.Array,    # [E] int32
    receivers: jax.Array,  # [E] int32, sorted ascending
    rev_perm: jax.Array,   # [E] int32 involution: edge -> its reverse
    plan_block,            # [T] int32 CSR work items, or None (XLA path)
    plan_chunk,
    plan_first,
    num_segments: int,
    with_dw: bool = True,  # False skips the weight gradient (static w)
) -> jax.Array:
    """out[r] = Σ_{e: receivers_e = r} w_e · h[senders_e]; see module doc."""
    return _sorted_segsum(w[:, None] * h[senders], receivers,
                          plan_block, plan_chunk, plan_first, num_segments)


def _agg_fwd(h, w, senders, receivers, rev_perm, pb, pc, pf,
             num_segments, with_dw):
    out = _sorted_segsum(w[:, None] * h[senders], receivers, pb, pc, pf,
                         num_segments)
    return out, (h, w, senders, receivers, rev_perm, pb, pc, pf)


def _agg_bwd(num_segments, with_dw, res, g):
    h, w, senders, receivers, rev_perm, pb, pc, pf = res
    g_s = g[senders]                     # cheap unsorted gather, [E, D]
    dh = _sorted_segsum(involute(rev_perm, w)[:, None] * g_s, receivers,
                        pb, pc, pf, num_segments)
    dw = (jnp.sum(g[receivers] * h[senders], axis=-1) if with_dw
          else jnp.zeros_like(w))
    return dh, dw, None, None, None, None, None, None


sym_segment_aggregate.defvjp(_agg_fwd, _agg_bwd)


# --- cluster-pair aggregation (kernels/cluster.py) with the same symmetric
# backward: clustered and straggler subsets are each closed under the edge
# involution (equal pair/mirror-pair counts), so dh runs the identical
# two-path program on (ḡ, w_bwd).  Mean aggregation only — weights are
# static per graph and precomputed host-side (including the reverse-edge
# weights, so the backward needs no index lookup).


class ClusterAgg:
    """Device arrays of a host `kernels.cluster.build_cluster_split`.

    Registered as a pytree so it can ride inside DeviceGraph.  Static
    plan shapes are leaves (int32 arrays), nothing auxiliary.  The
    optional straggler involution/mask (attention; see ClusterSplit doc)
    are None when the split was built without ``rev_perm``.
    """

    # gate for the attention cluster path (cluster_att_partial): the
    # r04 weight-ROUTING path was a wash at any realistic fraction
    # because its static gathers added [E] passes back; the r05 in-tile
    # kernels delete those, so the gate is just "enough clustered edges
    # to beat the kernel's own grid overhead" — the same shape as the
    # mean path's min_pair_edges threshold.  Measured r05 on-chip
    # (docs/benchmarks.md): at the bench graph's 39% clustered fraction
    # the split path runs the att step at 0.291 s vs 0.390 s without
    # (−25%); the win scales with the fraction, and the kernel grid is
    # tiny below ~15%, so the gate sits where the mean-path lever also
    # starts paying.
    ATT_MIN_FRAC = 0.15

    def __init__(self, c_recv, c_send, c_wf, c_wb, c_plan,
                 s_recv, s_send, s_wf, s_wb, s_plan,
                 s_rev_local=None, s_mask=None, use_att_cluster: bool = False):
        self.c_recv, self.c_send = c_recv, c_send
        self.c_wf, self.c_wb = c_wf, c_wb
        self.c_plan = c_plan
        self.s_recv, self.s_send = s_recv, s_send
        self.s_wf, self.s_wb = s_wf, s_wb
        self.s_plan = s_plan
        self.s_rev_local = s_rev_local
        self.s_mask = s_mask
        self.use_att_cluster = bool(use_att_cluster)

    @property
    def att_ok(self) -> bool:
        """Whether attention should take the in-tile cluster path:
        straggler involution present AND the clustered fraction clears
        ATT_MIN_FRAC (decided host-side at to_device time — static
        under jit)."""
        return self.s_rev_local is not None and self.use_att_cluster

    def tree_flatten(self):
        return ((self.c_recv, self.c_send, self.c_wf, self.c_wb,
                 tuple(self.c_plan), self.s_recv, self.s_send, self.s_wf,
                 self.s_wb, tuple(self.s_plan), self.s_rev_local,
                 self.s_mask),
                (self.use_att_cluster,))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, use_att_cluster=aux[0])

    @classmethod
    def from_host(cls, split):
        import jax.numpy as jnp

        dev = lambda a: None if a is None else jnp.asarray(a)
        return cls(dev(split.c_recv), dev(split.c_send), dev(split.c_wf),
                   dev(split.c_wb), tuple(dev(a) for a in split.c_plan),
                   dev(split.s_recv), dev(split.s_send), dev(split.s_wf),
                   dev(split.s_wb), tuple(dev(a) for a in split.s_plan),
                   dev(split.s_rev_local), dev(split.s_mask),
                   use_att_cluster=(split.frac_clustered
                                    >= cls.ATT_MIN_FRAC))


jax.tree_util.register_pytree_node(
    ClusterAgg,
    lambda c: c.tree_flatten(),
    lambda aux, leaves: ClusterAgg.tree_unflatten(aux, leaves))


def _cluster_two_path(h, wf_c, wf_s, agg: ClusterAgg, num_segments: int):
    from hyperspace_tpu.kernels.cluster import cluster_aggregate

    out = cluster_aggregate(h, wf_c, agg.c_recv, agg.c_send,
                            agg.c_plan, num_segments)
    msgs = wf_s.astype(h.dtype)[:, None] * h[agg.s_send]
    out = out + _sorted_segsum(msgs, agg.s_recv, *agg.s_plan,
                               num_segments).astype(out.dtype)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def cluster_sym_aggregate(h, agg: ClusterAgg, num_segments: int):
    """Mean aggregation through the cluster-pair kernel + straggler CSR.

    out[r] = Σ_e w_e h[senders_e] with w the precomputed 1/deg weights;
    ``h`` should already be cast to the aggregation dtype (bf16 messages
    halve the straggler traffic AND let the cluster kernel use the fast
    single-pass MXU mode).
    """
    return _cluster_two_path(h, agg.c_wf, agg.s_wf, agg, num_segments)


def _ca_fwd(h, agg, num_segments):
    return _cluster_two_path(h, agg.c_wf, agg.s_wf, agg, num_segments), agg


def _ca_bwd(num_segments, agg, g):
    # dh[i] = Σ_{e: r_e = i} w_{π(e)} ḡ[s_e] — identical program on
    # (ḡ, w_bwd); both subsets are reversal-closed so the split is exact
    dh = _cluster_two_path(g, agg.c_wb, agg.s_wb, agg, num_segments)
    return dh, None


cluster_sym_aggregate.defvjp(_ca_fwd, _ca_bwd)


# --- fused planned attention aggregation --------------------------------------
#
# The attention layer's cost on TPU is the [E]-length passes round the
# kernels, and what a pass costs depends on its form, not its bytes (v5e,
# 1.57 M straggler edges; PERF.md §5/§6): a row gather from a table in
# fast memory 2–5 ms at 33 bf16 lanes but 19 ms for the float32
# [E, 129] one, a 1-D scalar gather 11–15 ms, a key-sort 2.5 ms, a walk
# of the CSR plan 1 ms.  This op fuses the whole softmax-aggregate
# pipeline around ONE random edge gather:
#
# - forward: alpha_s rides as an extra feature column of h, so the
#   sender pick and the message gather are a single [E, F+1] gather; the
#   receiver-side pick alpha_r[receivers] is a walk of the CSR plan
#   (csr_segment_expand_1d), not a gather; logits/exp are one fused
#   elementwise pass (bounded-logit softmax — no max machinery, see
#   nn.gcn.bounded_att_logits); numerator and denominator are one
#   block-CSR pass.
# - backward: the gathered sender rows are SAVED as residuals (a
#   sequential [E, F] write+read, against a second random gather), so dw
#   needs no new random gather; the only random backward gather is
#   d_num[senders] for the involution dh; the two scalars the sender
#   direction needs of each edge's reverse (w, dpre) ride ONE key-sort
#   by the involution (involute); everything else is CSR passes.
#
# The op is a PARTIAL: it returns the unnormalized [N, F+1] (num | den)
# sums so a second partial over a different edge subset (the in-tile
# cluster kernel) can be added before the one division
# (:func:`att_combine`).  The full-edge-list composition is
# :func:`att_aggregate_planned`.


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def att_partial_planned(h, alpha_s, alpha_r, senders, receivers, rev_perm,
                        edge_mask, plan, num_segments: int, agg_dtype,
                        negative_slope: float):
    """Unnormalized attention partials on the planned layout:
    ``out[r] = Σ_e w_e·[h[s_e] | 1]`` (f32 [N, F+1]) with
    ``w_e = exp(bounded_logits(α_s[s_e]+α_r[r_e]))`` and 0 on masked
    edges.  ``edge_mask`` is the bool edge-validity mask (a constant of
    the graph — no cotangent).  Oracle: the unfused pick/exp/segsum
    chain in tests.
    """
    nd, _ = _att_partial_impl(h, alpha_s, alpha_r, senders, receivers,
                              edge_mask, plan, num_segments, agg_dtype,
                              negative_slope)
    return nd


def _att_partial_impl(h, alpha_s, alpha_r, senders, receivers, edge_mask,
                      plan, num_segments, agg_dtype, negative_slope):
    from hyperspace_tpu.nn.gcn import bounded_att_logits

    pb, pc, pf = plan
    f = h.shape[-1]
    ha = jnp.concatenate([h, alpha_s[:, None].astype(h.dtype)], axis=1)
    hs_a = ha[senders]                       # the ONE random gather
    h_s, a_se = hs_a[:, :f], hs_a[:, f]
    # the receiver-side pick happens inside the CSR walk, not as a gather
    a_re = csr_segment_expand_1d(alpha_r, receivers, plan, num_segments)
    lm = bounded_att_logits(a_se + a_re, negative_slope)
    w = jnp.where(edge_mask, jnp.exp(lm), 0.0)
    h_in = h_s if agg_dtype is None else h_s.astype(agg_dtype)
    w_in = w if agg_dtype is None else w.astype(agg_dtype)
    # numerator and denominator ride ONE CSR pass: the messages carry a
    # constant 1-column, so segsum(w·[h | 1]) = [Σ w·h | Σ w]
    msgs = jnp.concatenate(
        [w_in[:, None] * h_in, w_in[:, None]], axis=1)
    nd = _sorted_segsum(msgs, receivers, pb, pc, pf,
                        num_segments).astype(jnp.float32)
    return nd, (h_in, w_in, lm)


def _att_partial_fwd(h, alpha_s, alpha_r, senders, receivers, rev_perm,
                     edge_mask, plan, num_segments, agg_dtype,
                     negative_slope):
    nd, (h_in, w_in, lm) = _att_partial_impl(
        h, alpha_s, alpha_r, senders, receivers, edge_mask, plan,
        num_segments, agg_dtype, negative_slope)
    return nd, (h_in, w_in, lm, senders, receivers, rev_perm,
                edge_mask, plan, jnp.zeros((0,), h.dtype))


def _att_partial_bwd(num_segments, agg_dtype, negative_slope, res, g):
    from hyperspace_tpu.kernels.segment import (
        csr_att_bwd_edges,
        csr_segment_reduce_1d,
    )
    from hyperspace_tpu.nn.gcn import ATT_LOGIT_BOUND as B

    (h_in, w_in, lm, senders, receivers, rev_perm, edge_mask, plan,
     h_proto) = res
    h_dtype = h_proto.dtype
    f = h_in.shape[-1]
    pb, pc, pf = plan
    # the cotangent IS the fused d(num)|d(den) block ([N, F+1] f32):
    # ONE gather serves both directions (mirrors the forward's fused
    # num|den aggregation)
    dn_ext = g.astype(jnp.float32)
    dn_dt = dn_ext if agg_dtype is None else dn_ext.astype(agg_dtype)
    dn_s = dn_dt[senders]                # the one random backward gather
    # dw + softmax chain + d_alpha_r: ONE fused CSR pass — the receiver-
    # side d_num|d_den rows are picked from the resident node block, the
    # ones-augmented residual rows stream by chunk, and the per-receiver
    # reduction accumulates in the same walk (kernels/segment.py)
    # keep the residual stream in its storage dtype (bf16 halves the
    # [E, F+1] HBM read; the kernel upcasts per tile, and a ones column
    # is exact in any float dtype)
    h1 = jnp.concatenate(
        [h_in, jnp.ones_like(w_in, h_in.dtype)[:, None]], axis=1)
    dpre, d_alpha_r = csr_att_bwd_edges(
        dn_ext, h1, jnp.where(edge_mask, w_in.astype(jnp.float32), 0.0),
        lm, receivers, (pb, pc, pf), num_segments, float(B),
        negative_slope)
    # the two per-edge scalars the sender direction needs, w and dpre of
    # each edge's reverse, ride one key-sort (see involute)
    w_rev, dpre_rev = involute(rev_perm, w_in, dpre)
    # dh via the involution: sender-scatter becomes a receiver-scatter
    # (the extra lane aggregates Σ w·d_den — sliced off)
    dh = _sorted_segsum(w_rev[:, None] * dn_s, receivers,
                        pb, pc, pf, num_segments)[:, :f].astype(h_dtype)
    d_alpha_s = csr_segment_reduce_1d(dpre_rev, receivers,
                                      (pb, pc, pf), num_segments, op="sum")
    return (dh, d_alpha_s, d_alpha_r, None, None, None, None, None)


att_partial_planned.defvjp(_att_partial_fwd, _att_partial_bwd)


def att_combine(nd: jax.Array, out_dtype) -> jax.Array:
    """num/den of an [N, F+1] attention partial sum (the ONE division,
    applied after all edge-subset partials are added)."""
    num, den = nd[:, :-1], jnp.maximum(nd[:, -1], 1e-15)
    return (num / den[:, None]).astype(out_dtype)


def att_aggregate_planned(h, alpha_s, alpha_r, senders, receivers, rev_perm,
                          edge_mask, plan, num_segments: int, agg_dtype,
                          negative_slope: float):
    """Softmax-attention neighbor aggregation on the planned layout.

    ``out[r] = Σ_e softmax_r(bounded_logits(α_s[s_e]+α_r[r_e])) h[s_e]``
    — numerically identical to the unfused pick/exp/den/aggregate chain
    (the oracle in tests).  Composition of :func:`att_partial_planned`
    and :func:`att_combine` — autodiff of the division produces exactly
    the fused d(num)|d(den) cotangent the partial's VJP consumes.
    """
    nd = att_partial_planned(h, alpha_s, alpha_r, senders, receivers,
                             rev_perm, edge_mask, plan, num_segments,
                             agg_dtype, negative_slope)
    return att_combine(nd, h.dtype)


# --- in-tile attention on the cluster split -----------------------------------
#
# Clustered edges run the kernels/cluster.py fused attention kernels —
# logits, softmax weights, aggregation, and the whole backward computed
# from VMEM-resident endpoint blocks, so the clustered fraction of the
# graph never touches an [E]-length HBM stream in either direction.
# Stragglers run :func:`att_partial_planned` on their own (shorter)
# layout; the two [N, F+1] partials add and divide once.  This is the
# r05 replacement for the r04 weight-routing path, which was measured a
# wash because its static gathers added the [E] passes back.


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def cluster_att_partial(h, alpha_s, alpha_r, agg: ClusterAgg,
                        num_segments: int, negative_slope: float = 0.2):
    """[N, F+1] f32 unnormalized attention partials over the CLUSTERED
    edge subset, logits computed in-tile.  Requires ``agg.att_ok``.
    Twin/oracle: the gathered exp/segsum chain on (c_send, c_recv).
    """
    from hyperspace_tpu.kernels.cluster import cluster_att_fwd
    from hyperspace_tpu.nn.gcn import ATT_LOGIT_BOUND as B

    return cluster_att_fwd(h, alpha_s, alpha_r, agg.c_recv, agg.c_send,
                           agg.c_plan, num_segments, negative_slope,
                           float(B))


def _cap_fwd(h, alpha_s, alpha_r, agg, num_segments, negative_slope):
    return (cluster_att_partial(h, alpha_s, alpha_r, agg, num_segments,
                                negative_slope),
            (h, alpha_s, alpha_r, agg))


def _cap_bwd(num_segments, negative_slope, res, g):
    from hyperspace_tpu.kernels.cluster import cluster_att_bwd
    from hyperspace_tpu.nn.gcn import ATT_LOGIT_BOUND as B

    h, alpha_s, alpha_r, agg = res
    dh, da_s, da_r = cluster_att_bwd(
        g.astype(jnp.float32), h, alpha_s, alpha_r, agg.c_recv,
        agg.c_send, agg.c_plan, num_segments, negative_slope, float(B))
    return (dh.astype(h.dtype), da_s.astype(alpha_s.dtype),
            da_r.astype(alpha_r.dtype), None)  # agg: graph constant


cluster_att_partial.defvjp(_cap_fwd, _cap_bwd)
