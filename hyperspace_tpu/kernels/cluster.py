"""Cluster-pair SpMM aggregation — kill the [E, F] message round-trip.

The r02 anatomy probes (docs/benchmarks.md) showed the aggregation's
gather (`w·h[senders]`) is latency-bound and the block-CSR scatter reads
the materialized [E, F] messages back from HBM: every pass pays ~2·E·F
bytes of HBM traffic that exists only because the gather and the scatter
are separate XLA/Pallas ops.

This kernel processes edges grouped by (receiver-block, sender-block)
pairs and never materializes messages: with both endpoint blocks resident
in VMEM, a 128-edge sub-chunk becomes two MXU matmuls

    out_tile  +=  A @ (B @ h_tile)
    A[r_loc, e] = w_e      (edge-weighted receiver one-hot, [bn, 128])
    B[e, s_loc] = 1        (sender one-hot, [128, bs])

so HBM traffic is one h-tile load per (rb, sb) pair plus the edge id/
weight stream — for edges with block locality that is a fraction of
E·F.  Low-density pairs would waste a whole tile load on a few edges, so
the host splitter (`build_cluster_split`) routes only pairs with
``>= min_pair_edges`` through this kernel; the rest ("stragglers") keep
the existing gather + block-CSR path.  For a symmetrized edge list the
pair (a, b) and its mirror (b, a) have equal edge counts, so the split
is closed under edge reversal and the involution backward
(nn/scatter.py) survives on both paths.

Exactness: B@h is a pure row selection (each edge row has exactly one 1,
so no two nonzeros ever sum) — in bf16 the products and single-term sums
are exact, which is why the bf16 path can use the fast single-pass MXU
mode; accumulation is f32 throughout.  f32 inputs use HIGHEST precision
like kernels/segment.py.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hyperspace_tpu.kernels import _support as S

_BN = 256   # receiver-block rows (output tile)
_BS = 256   # sender-block rows (h tile).  bs=128/thr=64 wins the
# ISOLATED forward aggregation (24.1 vs 29.4 ms — smaller tiles make
# ~200-edge pairs profitable) but LOSES the full train step (0.146 vs
# 0.136 s clean-chip): in the full step XLA overlaps the straggler
# gather chain with other work, so shrinking it saves nothing while the
# larger cluster grid adds serial time.  Full-step wins set the default.
_BK = 512   # edges per chunk


class ClusterPlan(NamedTuple):
    """Work-item schedule for :func:`cluster_aggregate` (host-built).

    Items are receiver-block-major; ``first`` marks each rb's first item
    (the kernel zeroes the output tile there).  Every receiver block gets
    at least one item even if it owns no clustered edge.
    """

    rb: np.ndarray     # [T] item -> receiver-block index
    sb: np.ndarray     # [T] item -> sender-block index
    chunk: np.ndarray  # [T] item -> edge-chunk index
    first: np.ndarray  # [T] 1 iff first item of its receiver block


def build_cluster_plan(
    receivers: np.ndarray,  # [E] sorted by (rb, sb) within the clustered set
    senders: np.ndarray,    # [E] aligned
    num_nodes: int,
    bn: int = _BN,
    bs: int = _BS,
    bk: int = _BK,
) -> ClusterPlan:
    """Plan (rb, sb, chunk) items over edges pre-sorted by (rb, sb).

    Boundary chunks shared by two pairs are loaded by both and masked by
    the in-kernel local-range test (same trick as kernels/segment.py).
    """
    r = np.asarray(receivers)
    s = np.asarray(senders)
    e_pad = S.round_up(max(len(r), 1), bk)
    nchunks = e_pad // bk
    nb = -(-num_nodes // bn)
    key = (r // bn).astype(np.int64) * ((num_nodes // bs) + 1) + s // bs
    if len(key) > 1 and not np.all(np.diff(key) >= 0):
        raise ValueError("cluster plan needs edges sorted by (rb, sb)")
    # pair boundaries
    starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]]) if len(key) else np.zeros(0, np.int64)
    ends = np.r_[starts[1:], len(key)] if len(starts) else starts
    p_rb = (r[starts] // bn).astype(np.int32) if len(starts) else np.zeros(0, np.int32)
    p_sb = (s[starts] // bs).astype(np.int32) if len(starts) else np.zeros(0, np.int32)
    c0 = np.minimum(starts // bk, nchunks - 1)
    c1 = np.clip(-(-ends // bk), c0 + 1, nchunks)
    counts = (c1 - c0).astype(np.int64)

    rb_items = np.repeat(p_rb, counts)
    sb_items = np.repeat(p_sb, counts)
    chunk_items = (np.arange(counts.sum(), dtype=np.int64)
                   - np.repeat(np.cumsum(counts) - counts, counts)
                   + np.repeat(c0, counts)).astype(np.int32)

    # every receiver block needs >= 1 item so its output tile is zeroed;
    # dummy items point at chunk 0 whose edges (some other pair's) fail
    # the local-range test and contribute nothing
    present = np.zeros(nb, bool)
    present[p_rb] = True
    missing = np.flatnonzero(~present).astype(np.int32)
    rb_items = np.concatenate([rb_items, missing])
    sb_items = np.concatenate([sb_items, np.zeros(len(missing), np.int32)])
    chunk_items = np.concatenate([chunk_items, np.zeros(len(missing), np.int32)])

    order = np.argsort(rb_items, kind="stable")
    rb_items = rb_items[order].astype(np.int32)
    sb_items = sb_items[order].astype(np.int32)
    chunk_items = chunk_items[order].astype(np.int32)
    first = np.zeros(len(rb_items), np.int32)
    first[np.flatnonzero(np.r_[True, rb_items[1:] != rb_items[:-1]])] = 1
    return ClusterPlan(rb_items, sb_items, chunk_items, first)


def _body(bn: int, bs: int, fast_bf16: bool):
    prec = None if fast_bf16 else jax.lax.Precision.HIGHEST
    dt = jnp.bfloat16 if fast_bf16 else jnp.float32

    def body(rb_ref, sb_ref, chk_ref, first_ref, r_ref, s_ref, w_ref,
             h_ref, o_ref):
        t = pl.program_id(0)
        rb = rb_ref[t]
        sb = sb_ref[t]

        @pl.when(first_ref[t] == 1)
        def _():
            o_ref[:] = jnp.zeros_like(o_ref)

        r = r_ref[0]                    # [bk//128, 128] int32 (global)
        s = s_ref[0]
        w = w_ref[0].astype(jnp.float32)
        h_t = h_ref[:].astype(dt)       # [bs, F]
        acc = jnp.zeros_like(o_ref[:], jnp.float32)
        rows = jax.lax.broadcasted_iota(jnp.int32, (bn, 128), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (128, bs), 1)
        for j in range(r.shape[0]):
            ls = s[j] - sb * bs          # [128]; out-of-range matches nothing
            lr = r[j] - rb * bn
            b_oh = (cols == ls[:, None]).astype(dt)          # [128, bs]
            tmp = jnp.dot(b_oh, h_t, preferred_element_type=jnp.float32,
                          precision=prec)                    # [128, F] exact
            a_w = jnp.where(rows == lr[None, :], w[j][None, :], 0.0)
            acc += jnp.dot(a_w.astype(dt), tmp.astype(dt),
                           preferred_element_type=jnp.float32, precision=prec)
        o_ref[:] += acc

    return body


def cluster_aggregate(
    h: jax.Array,          # [N, F] node values
    w: jax.Array,          # [E] edge weights (0 on padding/masked edges)
    receivers: jax.Array,  # [E] int32 global, sorted by (rb, sb)
    senders: jax.Array,    # [E] int32 global, aligned
    plan: tuple,           # ClusterPlan device arrays (rb, sb, chunk, first)
    num_nodes: int,
    bn: int = _BN,
    bs: int = _BS,
    bk: int = _BK,
) -> jax.Array:
    """out[r] = Σ_{e: receivers_e = r} w_e · h[senders_e] without ever
    materializing [E, F] messages.  Twin/oracle: ``segment_sum`` of the
    gathered messages (any receiver order)."""
    m = S.mode()
    if m == "xla":
        acc_dt = jnp.promote_types(h.dtype, jnp.float32)
        msgs = (w[:, None] * h[senders]).astype(acc_dt)
        return jax.ops.segment_sum(msgs, receivers, num_nodes).astype(h.dtype)
    e = receivers.shape[0]
    if e == 0:
        # an empty clustered set still carries one dummy plan item per
        # receiver block; skipping the kernel (sum of nothing = 0) avoids
        # indexing chunk 0 of a zero-chunk edge array
        return jnp.zeros((num_nodes, h.shape[-1]), h.dtype)
    f = h.shape[-1]
    fp = S.round_up(f, 128)
    n_pad = S.round_up(num_nodes, max(bn, bs))
    h_p = S.pad_axis(S.pad_axis(h, -1, 128), 0, max(bn, bs))
    e_pad = S.round_up(e, bk)
    # pad ids out-of-range so padded lanes match no local row
    pad_ids = lambda a: jnp.pad(a, (0, e_pad - e), constant_values=n_pad)
    r2d = pad_ids(receivers).reshape(e_pad // bk, bk // 128, 128)
    s2d = pad_ids(senders).reshape(e_pad // bk, bk // 128, 128)
    w2d = jnp.pad(w.astype(jnp.float32), (0, e_pad - e)).reshape(
        e_pad // bk, bk // 128, 128)
    t = plan[0].shape[0]
    fast_bf16 = h.dtype == jnp.bfloat16
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, bk // 128, 128),
                         lambda t, rb, sb, chk, first: (chk[t], 0, 0)),
            pl.BlockSpec((1, bk // 128, 128),
                         lambda t, rb, sb, chk, first: (chk[t], 0, 0)),
            pl.BlockSpec((1, bk // 128, 128),
                         lambda t, rb, sb, chk, first: (chk[t], 0, 0)),
            pl.BlockSpec((bs, fp), lambda t, rb, sb, chk, first: (sb[t], 0)),
        ],
        out_specs=pl.BlockSpec((bn, fp),
                               lambda t, rb, sb, chk, first: (rb[t], 0)),
    )
    out = pl.pallas_call(
        _body(bn, bs, fast_bf16),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S.round_up(n_pad, bn), fp),
                                       jnp.float32),
        name="cluster_aggregate",
        interpret=S.interpret_flag(m),
    )(*tuple(plan)[:4], r2d, s2d, w2d, h_p)
    return out[:num_nodes, :f].astype(h.dtype)


# --- fused in-tile attention: logits computed from VMEM-resident blocks -------
#
# r04 measured the attention step's cost to be the COUNT of [E]-length
# HBM passes (~10–28 ms per 2.4 M-row pass, width-independent), and the
# r04 weighted cluster path was a wash precisely because routing runtime
# weights into the cluster layout added [E] gathers back.  The r05 fix:
# never materialize clustered-edge weights at all.  With both endpoint
# blocks resident in VMEM, the GAT logit α_s[s_e] + α_r[r_e] is two
# masked one-hot picks from [bs]/[bn] score vectors, the bounded-logit
# softmax weight exp(B·tanh(leaky(·)/B)) is VPU math, and the weighted
# aggregation is the same two-matmul program as the mean kernel — so
# clustered edges never touch the [E] stream in EITHER direction.  The
# forward emits unnormalized [num | den] partials ([N, F+1]); the
# straggler edges run the planned fused path and the division happens
# once on the combined [N, F+1] (nn/scatter.cluster_att_partial).
#
# The backward is one kernel producing dh AND both score gradients, all
# receiver-block-indexed via the edge involution (the clustered set is
# reversal-closed):
#
#   dh[i]   = Σ_{e: r_e=i} w_rev(e) · d_num[s_e]
#   dα_r[i] = Σ_{e: r_e=i} dpre_e
#   dα_s[i] = Σ_{e: r_e=i} dpre_rev(e)
#
# with w_rev(e) = f(α_s[r_e] + α_r[s_e]) (the reverse edge's weight —
# both alphas resident), dw_e = <d_num[r_e], h[s_e]> + d_den[r_e], and
# dpre = dw · w · f'(pre).  No [E]-aligned array exists anywhere.


def _att_squash(pre, bound, slope):
    """bounded_att_logits + its derivative, shared by both kernel bodies
    (mirrors nn.gcn.bounded_att_logits exactly)."""
    lam = jnp.where(pre >= 0, pre, slope * pre)
    th = jnp.tanh(lam / bound)
    w = jnp.exp(bound * th)
    dpre_factor = w * (1.0 - th * th) * jnp.where(pre >= 0, 1.0, slope)
    return w, dpre_factor


def _pick_grouped(vec_t, idx):
    """Per-edge pick from a resident score tile in its native layout.

    ``vec_t`` is [G, 128] f32 (a length-G·128 vector as loaded from its
    (1, G, 128) block), ``idx`` is [128] int32 local indices; returns the
    [128] picked values, 0 where idx is out of [0, G·128) — masked
    one-hot reduces only, no cross-lane reshape (Mosaic-safe).
    """
    g_idx = idx // 128
    l_idx = idx % 128
    rows = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
    sel = rows == l_idx[None, :]
    out = jnp.zeros((128,), jnp.float32)
    for g in range(vec_t.shape[0]):
        v = jnp.sum(jnp.where(sel, vec_t[g][:, None], 0.0), axis=0)
        out = out + jnp.where(g_idx == g, v, 0.0)
    return out


def _att_fwd_body(bn, bs, f, fp, fp_ext, fast_bf16, bound, slope):
    prec = None if fast_bf16 else jax.lax.Precision.HIGHEST
    dt = jnp.bfloat16 if fast_bf16 else jnp.float32

    def body(rb_ref, sb_ref, chk_ref, first_ref, r_ref, s_ref, h_ref,
             as_ref, ar_ref, o_ref):
        t = pl.program_id(0)
        rb = rb_ref[t]
        sb = sb_ref[t]

        @pl.when(first_ref[t] == 1)
        def _():
            o_ref[:] = jnp.zeros_like(o_ref)

        r = r_ref[0]                       # [bk//128, 128] int32 (global)
        s = s_ref[0]
        h_t = h_ref[:].astype(dt)          # [bs, fp]
        a_s_t = as_ref[0]                  # [bs//128, 128] f32 (senders)
        a_r_t = ar_ref[0]                  # [bn//128, 128] f32 (receivers)
        acc = jnp.zeros((bn, fp_ext), jnp.float32)
        rows = jax.lax.broadcasted_iota(jnp.int32, (bn, 128), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (128, bs), 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, fp_ext), 1)
        for j in range(r.shape[0]):
            ls = s[j] - sb * bs            # [128]; out-of-range matches 0
            lr = r[j] - rb * bn
            sel_r = rows == lr[None, :]    # [bn, 128]
            b_oh = (cols == ls[:, None]).astype(dt)      # [128, bs]
            # the in-tile logit: two masked picks + VPU squash (no [E]
            # stream); out-of-pair lanes (boundary chunks, padding ids)
            # are killed by the ls validity mask — sel_r alone would let
            # a same-rb neighbor pair's edge leak into the denominator
            pre = _pick_grouped(a_s_t, ls) + _pick_grouped(a_r_t, lr)
            w, _ = _att_squash(pre, bound, slope)
            w = jnp.where((ls >= 0) & (ls < bs), w, 0.0)
            tmp = jnp.dot(b_oh, h_t,                     # [128, fp] picks
                          preferred_element_type=jnp.float32,
                          precision=prec)
            # num|den ride one matmul: a constant-1 column at lane f
            if fp_ext > fp:
                extra = (jax.lax.broadcasted_iota(
                    jnp.int32, (128, fp_ext - fp), 1) == (f - fp)
                ).astype(jnp.float32)
                tmp_ext = jnp.concatenate([tmp, extra], axis=1)
            else:                          # h padding lanes are 0 -> safe
                tmp_ext = tmp + (lane == f).astype(jnp.float32)
            a_w = jnp.where(sel_r, w[None, :], 0.0)      # [bn, 128]
            acc += jnp.dot(a_w.astype(dt), tmp_ext.astype(dt),
                           preferred_element_type=jnp.float32,
                           precision=prec)
        o_ref[:] += acc

    return body


def cluster_att_fwd(
    h: jax.Array,          # [N, F] node values (agg dtype; bf16 = fast path)
    alpha_s: jax.Array,    # [N] sender attention scores
    alpha_r: jax.Array,    # [N] receiver attention scores
    receivers: jax.Array,  # [E] int32 global, sorted by (rb, sb)
    senders: jax.Array,    # [E] int32 global, aligned
    plan: tuple,           # ClusterPlan device arrays
    num_nodes: int,
    negative_slope: float = 0.2,
    bound: float = 30.0,
    bn: int = _BN,
    bs: int = _BS,
    bk: int = _BK,
) -> jax.Array:
    """[N, F+1] f32 unnormalized attention partials over the clustered
    edges: ``out[r] = Σ_e w_e·[h[s_e] | 1]`` with
    ``w_e = exp(bounded_att_logits(α_s[s_e]+α_r[r_e]))`` computed
    IN-TILE.  Twin/oracle: exp/mask/segment-sum of the gathered chain.
    """
    f = h.shape[-1]
    m = S.mode()
    e = receivers.shape[0]
    if m == "xla" or e == 0:
        if e == 0:
            return jnp.zeros((num_nodes, f + 1), jnp.float32)
        pre = (alpha_s.astype(jnp.float32)[senders]
               + alpha_r.astype(jnp.float32)[receivers])
        w, _ = _att_squash(pre, bound, negative_slope)
        w = w.astype(h.dtype).astype(jnp.float32)  # match kernel rounding
        msgs = jnp.concatenate(
            [w[:, None] * h[senders].astype(jnp.float32), w[:, None]],
            axis=1)
        return jax.ops.segment_sum(msgs, receivers, num_nodes)
    fp = S.round_up(f, 128)
    fp_ext = S.round_up(f + 1, 128)
    n_pad = S.round_up(num_nodes, max(bn, bs))
    h_p = S.pad_axis(S.pad_axis(h, -1, 128), 0, max(bn, bs))
    a_s2 = jnp.pad(alpha_s.astype(jnp.float32),
                   (0, n_pad - num_nodes)).reshape(n_pad // bs,
                                                   bs // 128, 128)
    a_r2 = jnp.pad(alpha_r.astype(jnp.float32),
                   (0, n_pad - num_nodes)).reshape(n_pad // bn,
                                                   bn // 128, 128)
    e_pad = S.round_up(e, bk)
    pad_ids = lambda a: jnp.pad(a, (0, e_pad - e), constant_values=n_pad)
    r2d = pad_ids(receivers).reshape(e_pad // bk, bk // 128, 128)
    s2d = pad_ids(senders).reshape(e_pad // bk, bk // 128, 128)
    t = plan[0].shape[0]
    fast_bf16 = h.dtype == jnp.bfloat16
    chunk_spec = pl.BlockSpec((1, bk // 128, 128),
                              lambda t, rb, sb, chk, first: (chk[t], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(t,),
        in_specs=[
            chunk_spec, chunk_spec,
            pl.BlockSpec((bs, fp), lambda t, rb, sb, chk, first: (sb[t], 0)),
            pl.BlockSpec((1, bs // 128, 128),
                         lambda t, rb, sb, chk, first: (sb[t], 0, 0)),
            pl.BlockSpec((1, bn // 128, 128),
                         lambda t, rb, sb, chk, first: (rb[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, fp_ext),
                               lambda t, rb, sb, chk, first: (rb[t], 0)),
    )
    out = pl.pallas_call(
        _att_fwd_body(bn, bs, f, fp, fp_ext, fast_bf16,
                      float(bound), float(negative_slope)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S.round_up(n_pad, bn), fp_ext),
                                       jnp.float32),
        name="cluster_att_fwd",
        interpret=S.interpret_flag(m),
    )(*tuple(plan)[:4], r2d, s2d, h_p, a_s2, a_r2)
    return out[:num_nodes, : f + 1]


def _att_bwd_body(bn, bs, f, fp, fp_ext, fp_out, fast_bf16, bound, slope):
    prec = None if fast_bf16 else jax.lax.Precision.HIGHEST
    dt = jnp.bfloat16 if fast_bf16 else jnp.float32

    def body(rb_ref, sb_ref, chk_ref, first_ref, r_ref, s_ref,
             g_rb_ref, g_sb_ref, h_rb_ref, h_sb_ref,
             as_rb_ref, as_sb_ref, ar_rb_ref, ar_sb_ref, o_ref):
        t = pl.program_id(0)
        rb = rb_ref[t]
        sb = sb_ref[t]

        @pl.when(first_ref[t] == 1)
        def _():
            o_ref[:] = jnp.zeros_like(o_ref)

        r = r_ref[0]
        s = s_ref[0]
        g_rb = g_rb_ref[:].astype(dt)        # [bn, fp_ext] d_num|d_den
        g_sb = g_sb_ref[:].astype(dt)        # [bs, fp_ext]
        h_rb = h_rb_ref[:].astype(dt)        # [bn, fp]
        h_sb = h_sb_ref[:].astype(dt)        # [bs, fp]
        a_s_rb = as_rb_ref[0]                # [bn//128, 128] f32
        a_s_sb = as_sb_ref[0]                # [bs//128, 128]
        a_r_rb = ar_rb_ref[0]
        a_r_sb = ar_sb_ref[0]
        acc = jnp.zeros((bn, fp_out), jnp.float32)
        rows = jax.lax.broadcasted_iota(jnp.int32, (bn, 128), 0)
        cols_s = jax.lax.broadcasted_iota(jnp.int32, (128, bs), 1)
        cols_r = jax.lax.broadcasted_iota(jnp.int32, (128, bn), 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, fp_out), 1)
        num_lanes = (jax.lax.broadcasted_iota(jnp.int32, (1, fp), 1)
                     < f).astype(jnp.float32)
        for j in range(r.shape[0]):
            ls = s[j] - sb * bs
            lr = r[j] - rb * bn
            sel_r = rows == lr[None, :]      # [bn, 128]
            valid = ((ls >= 0) & (ls < bs) & (lr >= 0) & (lr < bn)
                     ).astype(jnp.float32)
            b_oh = (cols_s == ls[:, None]).astype(dt)   # [128, bs]
            r_oh = (cols_r == lr[:, None]).astype(dt)   # [128, bn]
            gs = jnp.dot(b_oh, g_sb, preferred_element_type=jnp.float32,
                         precision=prec)     # [128, fp_ext]  rows d[s_e]
            gr = jnp.dot(r_oh, g_rb, preferred_element_type=jnp.float32,
                         precision=prec)     # [128, fp_ext]  rows d[r_e]
            hs = jnp.dot(b_oh, h_sb, preferred_element_type=jnp.float32,
                         precision=prec)     # [128, fp]      rows h[s_e]
            hr = jnp.dot(r_oh, h_rb, preferred_element_type=jnp.float32,
                         precision=prec)     # [128, fp]      rows h[r_e]
            # dw_e = <d_num[r_e], h[s_e]> + d_den[r_e]; the h padding
            # lanes are 0, so full-width products exclude lane f safely
            dw = jnp.sum(gr[:, :fp] * hs, axis=1) + gr[:, f]
            dw_rev = jnp.sum(gs[:, :fp] * hr, axis=1) + gs[:, f]
            pre = _pick_grouped(a_s_sb, ls) + _pick_grouped(a_r_rb, lr)
            pre_rev = (_pick_grouped(a_s_rb, lr)
                       + _pick_grouped(a_r_sb, ls))
            w, dfac = _att_squash(pre, bound, slope)
            w_rev, dfac_rev = _att_squash(pre_rev, bound, slope)
            dpre = dw * dfac * valid
            dpre_rev = dw_rev * dfac_rev * valid
            # dh[r] += w_rev · d_num[s]: mask d_den out of the gs rows,
            # keep only the first f lanes live
            gs_num = gs[:, :fp] * num_lanes
            if fp_out > fp:
                gs_num = jnp.concatenate(
                    [gs_num, jnp.zeros((128, fp_out - fp), jnp.float32)],
                    axis=1)
            a_w_rev = jnp.where(sel_r, (w_rev * valid)[None, :], 0.0)
            acc += jnp.dot(a_w_rev.astype(dt), gs_num.astype(dt),
                           preferred_element_type=jnp.float32,
                           precision=prec)
            # score gradients ride lanes f (dα_r) and f+1 (dα_s)
            da_r = jnp.sum(jnp.where(sel_r, dpre[None, :], 0.0), axis=1)
            da_s = jnp.sum(jnp.where(sel_r, dpre_rev[None, :], 0.0),
                           axis=1)
            acc += (da_r[:, None] * (lane == f)
                    + da_s[:, None] * (lane == f + 1))
        o_ref[:] += acc

    return body


def cluster_att_bwd(
    g_ext: jax.Array,      # [N, F+1] f32 cotangent (d_num | d_den)
    h: jax.Array,          # [N, F] node values (same array as forward)
    alpha_s: jax.Array,    # [N]
    alpha_r: jax.Array,    # [N]
    receivers: jax.Array,  # [E] int32 global, sorted by (rb, sb)
    senders: jax.Array,    # [E]
    plan: tuple,
    num_nodes: int,
    negative_slope: float = 0.2,
    bound: float = 30.0,
    bn: int = _BN,
    bs: int = _BS,
    bk: int = _BK,
):
    """Backward of :func:`cluster_att_fwd`: returns
    ``(dh [N, F] f32, d_alpha_s [N] f32, d_alpha_r [N] f32)`` — one
    kernel, everything receiver-block-indexed via the edge involution
    (module comment above).  Twin/oracle: jax.vjp of the gathered chain.
    """
    f = h.shape[-1]
    m = S.mode()
    e = receivers.shape[0]
    if m == "xla" or e == 0:
        if e == 0:
            z = jnp.zeros((num_nodes,), jnp.float32)
            return jnp.zeros((num_nodes, f), jnp.float32), z, z

        def fwd(hh, a_s, a_r):
            pre = a_s[senders] + a_r[receivers]
            w, _ = _att_squash(pre, bound, negative_slope)
            w = w.astype(hh.dtype).astype(jnp.float32)
            msgs = jnp.concatenate(
                [w[:, None] * hh.astype(jnp.float32)[senders], w[:, None]],
                axis=1)
            return jax.ops.segment_sum(msgs, receivers, num_nodes)

        _, vjp = jax.vjp(fwd, h, alpha_s.astype(jnp.float32),
                         alpha_r.astype(jnp.float32))
        dh, da_s, da_r = vjp(g_ext.astype(jnp.float32))
        return dh.astype(jnp.float32), da_s, da_r
    fp = S.round_up(f, 128)
    fp_ext = S.round_up(f + 1, 128)
    fp_out = S.round_up(f + 2, 128)
    n_pad = S.round_up(num_nodes, max(bn, bs))
    g_p = S.pad_axis(S.pad_axis(g_ext.astype(jnp.float32), -1, 128),
                     0, max(bn, bs))
    h_p = S.pad_axis(S.pad_axis(h, -1, 128), 0, max(bn, bs))
    a_pad = lambda a: jnp.pad(a.astype(jnp.float32), (0, n_pad - num_nodes))
    a_s_sb = a_pad(alpha_s).reshape(n_pad // bs, bs // 128, 128)
    a_s_rb = a_pad(alpha_s).reshape(n_pad // bn, bn // 128, 128)
    a_r_sb = a_pad(alpha_r).reshape(n_pad // bs, bs // 128, 128)
    a_r_rb = a_pad(alpha_r).reshape(n_pad // bn, bn // 128, 128)
    e_pad = S.round_up(e, bk)
    pad_ids = lambda a: jnp.pad(a, (0, e_pad - e), constant_values=n_pad)
    r2d = pad_ids(receivers).reshape(e_pad // bk, bk // 128, 128)
    s2d = pad_ids(senders).reshape(e_pad // bk, bk // 128, 128)
    t = plan[0].shape[0]
    fast_bf16 = h.dtype == jnp.bfloat16
    chunk_spec = pl.BlockSpec((1, bk // 128, 128),
                              lambda t, rb, sb, chk, first: (chk[t], 0, 0))
    rb_spec = lambda w_: pl.BlockSpec(
        (bn, w_), lambda t, rb, sb, chk, first: (rb[t], 0))
    sb_spec = lambda w_: pl.BlockSpec(
        (bs, w_), lambda t, rb, sb, chk, first: (sb[t], 0))
    vec_rb = pl.BlockSpec((1, bn // 128, 128),
                          lambda t, rb, sb, chk, first: (rb[t], 0, 0))
    vec_sb = pl.BlockSpec((1, bs // 128, 128),
                          lambda t, rb, sb, chk, first: (sb[t], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(t,),
        in_specs=[
            chunk_spec, chunk_spec,
            rb_spec(fp_ext), sb_spec(fp_ext),      # g at rb, sb
            rb_spec(fp), sb_spec(fp),              # h at rb, sb
            vec_rb, vec_sb,                        # alpha_s at rb, sb
            vec_rb, vec_sb,                        # alpha_r at rb, sb
        ],
        out_specs=pl.BlockSpec((bn, fp_out),
                               lambda t, rb, sb, chk, first: (rb[t], 0)),
    )
    out = pl.pallas_call(
        _att_bwd_body(bn, bs, f, fp, fp_ext, fp_out, fast_bf16,
                      float(bound), float(negative_slope)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S.round_up(n_pad, bn), fp_out),
                                       jnp.float32),
        name="cluster_att_bwd",
        interpret=S.interpret_flag(m),
    )(*tuple(plan)[:4], r2d, s2d, g_p, g_p, h_p, h_p,
      a_s_rb, a_s_sb, a_r_rb, a_r_sb)
    return (out[:num_nodes, :f], out[:num_nodes, f + 1],
            out[:num_nodes, f])


# --- host-side split: clustered pairs vs stragglers ---------------------------


class ClusterSplit(NamedTuple):
    """Host result of :func:`build_cluster_split` (numpy; see to_device).

    Clustered edges (pair density >= threshold) carry a ClusterPlan;
    stragglers keep the receiver-sorted layout + block-CSR plan of the
    main path.  ``w_*`` are the static mean-aggregation weights of each
    edge and of its reverse (1/deg of the opposite endpoint) — the
    involution backward needs no index lookup (same trick as
    parallel/node_shard.py).

    For attention (nn/scatter.cluster_att_partial) the clustered edges
    run the in-tile kernels above (which need nothing beyond the ids),
    and the STRAGGLER edges run the planned fused attention path on
    their own layout — which needs a self-contained edge involution:
    ``s_rev_local[i]`` is the straggler-array position of edge i's
    reverse (the straggler set is reversal-closed because pair (a, b)
    and its mirror (b, a) always share a density class; padding rows map
    to themselves).  ``s_mask`` is the bool validity mask of the padded
    straggler rows.  Both are None when the split was built without
    ``rev_perm`` (attention-on-cluster then unsupported).
    """

    c_recv: np.ndarray   # [Ec] clustered receivers, (rb, sb)-sorted
    c_send: np.ndarray   # [Ec]
    c_wf: np.ndarray     # [Ec] 1/deg[recv]
    c_wb: np.ndarray     # [Ec] 1/deg[send]
    c_plan: ClusterPlan
    s_recv: np.ndarray   # [Es] straggler receivers, ascending
    s_send: np.ndarray   # [Es]
    s_wf: np.ndarray
    s_wb: np.ndarray
    s_plan: tuple        # block-CSR plan for the straggler receivers
    frac_clustered: float
    s_rev_local: np.ndarray | None = None  # [Es] straggler involution
    s_mask: np.ndarray | None = None       # [Es] bool, 1 on real rows


def build_cluster_split(
    senders: np.ndarray,
    receivers: np.ndarray,  # ascending (prepare layout)
    edge_mask: np.ndarray,
    deg: np.ndarray,
    num_nodes: int,
    bn: int = _BN,
    bs: int = _BS,
    bk: int = _BK,
    min_pair_edges: int = 256,
    rev_perm: np.ndarray | None = None,
) -> ClusterSplit:
    if bn != bs:
        # the straggler/clustered partition is closed under edge reversal
        # ONLY when receivers and senders use identical blockings: edge
        # (a, b) lands in pair (a//bn, b//bs) and its mirror (b, a) in
        # (b//bn, a//bs), which are each other's transposes — hence the
        # same edge count / density class — iff bn == bs.  The attention
        # backward's involution identities (s_rev_local, cluster_att_bwd)
        # require that closure; with bn != bs it fails as an
        # AssertionError deep inside prepare(), so reject up front.
        raise ValueError(
            f"build_cluster_split requires bn == bs (got bn={bn}, "
            f"bs={bs}): reversal closure of the clustered/straggler "
            "split — and with it the attention path's straggler "
            "involution — only holds under identical receiver/sender "
            "blockings")
    from hyperspace_tpu.kernels.segment import build_csr_plan

    mask = np.asarray(edge_mask)
    pos = np.flatnonzero(mask)              # prepare-layout index per edge
    r = np.asarray(receivers)[mask]
    s = np.asarray(senders)[mask]
    d = np.maximum(np.asarray(deg), 1.0).astype(np.float32)
    nsb = num_nodes // bs + 1
    key = (r // bn).astype(np.int64) * nsb + s // bs
    order = np.argsort(key, kind="stable")
    key_s = key[order]
    uniq, inv, counts = np.unique(key_s, return_inverse=True,
                                  return_counts=True)
    dense = counts[inv] >= min_pair_edges   # per sorted edge
    c_idx = order[dense]
    s_idx = np.sort(order[~dense])          # back to receiver-ascending
    c_recv, c_send = r[c_idx], s[c_idx]
    s_recv, s_send = r[s_idx], s[s_idx]

    c_plan = build_cluster_plan(c_recv, c_send, num_nodes, bn, bs, bk)
    # straggler CSR plan wants every node block covered; sentinel-pad to
    # keep receivers sorted (padding edges carry w = 0)
    e_s = S.round_up(max(len(s_recv), 1), bk)
    s_recv_p = np.full(e_s, num_nodes - 1, np.int32)
    s_send_p = np.zeros(e_s, np.int32)
    s_wf = np.zeros(e_s, np.float32)
    s_wb = np.zeros(e_s, np.float32)
    s_recv_p[: len(s_recv)] = s_recv
    s_send_p[: len(s_send)] = s_send
    s_wf[: len(s_recv)] = 1.0 / d[s_recv]
    s_wb[: len(s_recv)] = 1.0 / d[s_send]
    s_plan = tuple(build_csr_plan(s_recv_p, num_nodes, bn=128, bk=bk))

    # straggler-local involution (ClusterSplit doc): lets the planned
    # fused attention path run self-contained on the straggler layout
    maps: dict = {}
    if rev_perm is not None:
        rp = np.asarray(rev_perm)
        loc = np.full(len(mask), -1, np.int64)   # prepare idx -> slot
        loc[pos[s_idx]] = np.arange(len(s_idx))
        s_rev_local = np.arange(e_s, dtype=np.int32)  # padding: self-map
        s_rev_local[: len(s_idx)] = loc[rp[pos[s_idx]]]
        if len(s_idx) and s_rev_local[: len(s_idx)].min() < 0:
            raise AssertionError(
                "straggler set not closed under edge reversal")
        s_mask = np.zeros(e_s, bool)
        s_mask[: len(s_idx)] = True
        maps = dict(s_rev_local=s_rev_local, s_mask=s_mask)

    return ClusterSplit(
        c_recv=c_recv.astype(np.int32), c_send=c_send.astype(np.int32),
        c_wf=(1.0 / d[c_recv]), c_wb=(1.0 / d[c_send]),
        c_plan=c_plan,
        s_recv=s_recv_p, s_send=s_send_p, s_wf=s_wf, s_wb=s_wb,
        s_plan=s_plan,
        frac_clustered=float(len(c_recv)) / max(len(r), 1),
        **maps,
    )
