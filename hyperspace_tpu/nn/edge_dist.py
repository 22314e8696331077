"""The LP decoder's pair distances with VJPs that avoid XLA's scatter-add.

The decoder's backward sums millions of per-pair cotangent rows into the
[N, D] embedding.  XLA's scatter-add does that at about one row per
14 ns: at the ogbn-arxiv shape two scatters of ``bf16[1880610, 33]``
into ``bf16[169343, 33]`` cost 2 × 27.1 ms, with their index sorts 43%
of the mean arm's 136.5 ms step and the largest single cost of both arms
(PERF_LEDGER.jsonl, PR 26).  Everything here keeps the *math* of
``manifold.sqdist`` untouched (the backward re-runs its own VJP per
pair: clamps, custom gradients and the curvature cotangent included)
and changes only where the rows are computed and how they are summed.
What each op asks of the step around it differs:

- :func:`pair_sqdist` — ANY pairs, new on every step.  The backward
  sorts the pair ends on the device, recomputes the cotangent rows in
  node order from rows re-gathered out of ``z``, plans on the device and
  sums with a block-CSR kernel.  Changes nothing about the step: same
  sampler, same pairs, same order, same forward.  ``train_step_lp``
  (``cli.train``, both benchmark cells) runs on it.
- :func:`graph_edge_sqdist` — distances along the training graph's own
  symmetrised message edges (receiver-sorted, reverse-edge involution π,
  host-built plan): both endpoint scatters become one planned sum.
  **Changes the pair set**: the positives are the message edges, self
  loops weighted out, not ``train_pos``.
- :func:`pair_sqdist_semi_planned` — (u, v) pairs whose u column is
  static and sorted with a host-built plan.  **Changes the sampler**:
  negatives corrupt one side only, u drawn once for the run
  (``models.hgcn.make_static_negatives``).
- :func:`pair_sqdist_planned` — both columns static, both planned.
  Positives only (the run's ``train_pos``, sorted by u on the host:
  **changes the pair order**).

The last three serve ``train_step_lp_planned`` / ``train_step_lp_pairs``,
which therefore compute another step than the configuration's: the
benchmark's reference draws ``[n_neg, 2]`` uniform pairs from the step
key and scores ``train_pos``, so neither may stand in for
``train_step_lp`` under a cell.  No chip run has timed them (ROADMAP.md
Speed 1).  Each op returns the same values and gradients as
``m.sqdist(z[a], z[b])`` on its pairs (tests/nn/test_edge_dist.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from hyperspace_tpu.kernels.segment import (
    pair_scatter_sum,
    rows_for_device_plan,
    rows_to_columns,
)
from hyperspace_tpu.nn.scatter import _sorted_segsum


def _sqdist_fn(kind: str):
    from hyperspace_tpu.nn.gcn import make_manifold

    def f(a, b, c):
        return make_manifold(kind, c).sqdist(a, b)

    return f


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def pair_sqdist(
    z: jax.Array,   # [N, D] points on the manifold
    c,              # curvature (traced scalar; grads flow)
    u: jax.Array,   # [P] int32 in [0, N), any order, new on every step
    v: jax.Array,   # [P] int32, likewise
    kind: str = "lorentz",
) -> jax.Array:
    """sqdist(z[u_p], z[v_p]) whose VJP needs no plan from the host and
    no scatter-add: see :func:`_pair_bwd`.

    The gain rests on XLA keeping ``z`` in fast memory for the backward's
    re-gather, which this code cannot observe.  Timed on a v5e against
    XLA's scatter-adds, forward + backward, ``z`` of 33 bf16 lanes alone
    (PERF.md §6, PR 27): level at 2,708 rows and 8,976 pairs (0.49
    against 0.45 ms), ahead from 19,717 rows to 338,686 (43.4 against
    74.5 at 1,880,610 pairs) and again from 1,354,744 rows up, where the
    scatter-add itself collapses (185 against 553); **behind at 677,372
    rows (128.4 against 88.8)**, the one point timed in between.  Before a graph of 0.4-1.3 M nodes, or
    another width, trains on it: time it (scripts/sweep_pair_sqdist.py)
    and, if it loses, choose by the static ``z.shape`` at the call in
    ``models/hgcn.py``."""
    return _sqdist_fn(kind)(z[u], z[v], c)


def _pair_fwd(z, c, u, v, kind):
    return pair_sqdist(z, c, u, v, kind), (z, c, u, v)


def _pair_bwd(kind, res, gbar):
    """dz[n] sums, over every pair end that is n, that end's cotangent
    row.  Moving 3.8 M rows into node order costs XLA 10-16 ns a row on
    a v5e whatever the op (gather, scatter, a sort that carries them:
    PERF.md §6, PR 27), but rows gathered from the SMALL ``z`` cost 1.6.
    So nothing wide moves: each pair is listed twice, once for each end,
    (end's node, other end's node and which end, cotangent) is sorted by
    the end's node as three 1-D arrays, and the rows are computed in that
    order by sqdist's own VJP at the re-gathered points — the u end's row
    from its first argument, the v end's from its second, so they are the
    rows autodiff computes.  The block-CSR kernel then sums the sorted
    rows (`kernels.segment.pair_scatter_sum`, planned on the device);
    float32 accumulation, one cast."""
    z, c, u, v = res
    f, n, p = _sqdist_fn(kind), z.shape[0], u.shape[0]
    # behind the data: the whole chunk of zero rows the device plan's
    # unused items point at.  Id n sorts last; cotangent 0 makes the row 0
    e = rows_for_device_plan(2 * p)
    pad = lambda x, fill: jnp.pad(x, (0, e - 2 * p), constant_values=fill)
    ctr, oth, gb = jax.lax.sort(
        (pad(jnp.concatenate([u, v]), n),
         pad(jnp.concatenate([2 * v, 2 * u + 1]), 0),  # low bit: the v end
         pad(jnp.concatenate([gbar, gbar]), 0)),
        num_keys=1, is_stable=False)  # equal ids only reorder a f32 sum
    ctr_in, is_v, oth = jnp.minimum(ctr, n - 1), oth % 2 == 1, oth // 2
    # ONE gather for both ends: XLA gathers at 1.6 ns a row only from a
    # table it keeps in fast memory, and of two copies of z one may not fit
    zz = rows_to_columns(z[jnp.concatenate([jnp.where(is_v, oth, ctr_in),
                                            jnp.where(is_v, ctr_in, oth)])])
    _, vjp = jax.vjp(f, zz[:, :e].T, zz[:, e:].T, c)
    g_u, g_v, dc_twice = vjp(gb)  # every pair is in the list twice
    rows = jnp.where(is_v[:, None], g_v, g_u)
    dz = pair_scatter_sum(rows.T, ctr, n).T
    return dz.astype(z.dtype), dc_twice / 2, None, None


pair_sqdist.defvjp(_pair_fwd, _pair_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def graph_edge_sqdist(
    z: jax.Array,          # [N, D] points on the manifold
    c,                     # curvature (traced scalar; grads flow)
    senders: jax.Array,    # [E] int32
    receivers: jax.Array,  # [E] int32, sorted ascending
    rev_perm: jax.Array,   # [E] int32 involution edge -> reverse edge
    plan_block,            # CSR work items ([T] int32 each) or None
    plan_chunk,
    plan_first,
    kind: str = "lorentz",
) -> jax.Array:
    """sqdist(z[s_e], z[r_e]) per edge, with a single planned VJP scatter."""
    return _sqdist_fn(kind)(z[senders], z[receivers], c)


def _ge_fwd(z, c, s, r, rp, pb, pc, pf, kind):
    return graph_edge_sqdist(z, c, s, r, rp, pb, pc, pf, kind), (
        z, c, s, r, rp, pb, pc, pf)


def _ge_bwd(kind, res, gbar):
    z, c, s, r, rp, pb, pc, pf = res
    zs, zr = z[s], z[r]
    # Distance symmetry collapses both endpoint cotangents into ONE
    # receiver-side partial: with D(a,b) = ∂sqdist(a,b)/∂b (= ∂/∂a at the
    # swapped pair, since sqdist(a,b) = sqdist(b,a)), the sender-side
    # cotangent of edge e lands at edge π(e) as
    #     gs_{π(e)} = D(zr_e, zs_e) · ḡ_{π(e)} ,
    # i.e. the SAME per-edge vector as gr_e scaled by the π-permuted
    # scalar — so only the [E] cotangent permutes, never an [E, D] tensor
    # (a full-row permute gather costs 124 ms at arxiv scale; the scalar
    # one is free).
    _, vjp_r = jax.vjp(lambda b: _sqdist_fn(kind)(zs, b, c), zr)
    (gr_both,) = vjp_r(gbar + gbar[rp])
    dz = _sorted_segsum(gr_both, r, pb, pc, pf, z.shape[0])
    # curvature cotangent uses the original ḡ (c is not edge-indexed)
    _, vjp_c = jax.vjp(lambda cc: _sqdist_fn(kind)(zs, zr, cc), c)
    (dc,) = vjp_c(gbar)
    return dz.astype(z.dtype), dc, None, None, None, None, None, None


graph_edge_sqdist.defvjp(_ge_fwd, _ge_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def pair_sqdist_semi_planned(
    z: jax.Array,   # [N, D]
    c,
    u: jax.Array,   # [P] int32, sorted ascending, static across steps
    v: jax.Array,   # [P] int32, arbitrary (fresh randomness each step)
    plan_block,     # CSR plan for u, or None
    plan_chunk,
    plan_first,
    kind: str = "lorentz",
) -> jax.Array:
    """sqdist(z[u_p], z[v_p]) with the u-side VJP scatter planned."""
    return _sqdist_fn(kind)(z[u], z[v], c)


def _ps_fwd(z, c, u, v, pb, pc, pf, kind):
    return pair_sqdist_semi_planned(z, c, u, v, pb, pc, pf, kind), (
        z, c, u, v, pb, pc, pf)


def _ps_bwd(kind, res, gbar):
    z, c, u, v, pb, pc, pf = res
    _, vjp = jax.vjp(_sqdist_fn(kind), z[u], z[v], c)
    gu, gv, dc = vjp(gbar)
    dz = _sorted_segsum(gu, u, pb, pc, pf, z.shape[0])
    # v side is fresh randomness each step — unsorted scatter is the cost
    # of that; accumulate it in ≥f32 so bf16 cotangents don't truncate
    acc_dt = jnp.promote_types(gv.dtype, jnp.float32)
    dz = dz.astype(acc_dt) + jax.ops.segment_sum(
        gv.astype(acc_dt), v, z.shape[0])
    return dz.astype(z.dtype), dc, None, None, None, None, None


pair_sqdist_semi_planned.defvjp(_ps_fwd, _ps_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(12,))
def pair_sqdist_planned(
    z: jax.Array,   # [N, D]
    c,
    u: jax.Array,   # [P] int32, sorted ascending, static across steps
    v: jax.Array,   # [P] int32, static across steps (any order)
    u_pb, u_pc, u_pf,   # CSR plan for u
    v_perm: jax.Array,  # [P] int32 static argsort of v
    v_sorted: jax.Array,  # [P] = v[v_perm]
    v_pb, v_pc, v_pf,   # CSR plan for v_sorted
    kind: str = "lorentz",
) -> jax.Array:
    """sqdist(z[u_p], z[v_p]) with BOTH VJP scatters planned.

    For *static* pair sets (e.g. the training positives, fixed for a whole
    run) the v column can be pre-sorted too: the backward permutes the
    v-side cotangents through the static ``v_perm`` and feeds them to the
    same sorted block-CSR scatter as the u side — no unsorted scatter
    anywhere in the decoder (VERDICT r1 #6: fold the Fermi–Dirac decoder's
    distance pass into the planned kernel).  Build the inputs once with
    ``models.hgcn.make_planned_pairs``.
    """
    return _sqdist_fn(kind)(z[u], z[v], c)


def _pair_planned_fwd(z, c, u, v, u_pb, u_pc, u_pf, v_perm, v_sorted,
                      v_pb, v_pc, v_pf, kind):
    out = pair_sqdist_planned(z, c, u, v, u_pb, u_pc, u_pf, v_perm,
                              v_sorted, v_pb, v_pc, v_pf, kind)
    return out, (z, c, u, v, u_pb, u_pc, u_pf, v_perm, v_sorted,
                 v_pb, v_pc, v_pf)


def _pair_planned_bwd(kind, res, gbar):
    (z, c, u, v, u_pb, u_pc, u_pf, v_perm, v_sorted, v_pb, v_pc, v_pf) = res
    _, vjp = jax.vjp(_sqdist_fn(kind), z[u], z[v], c)
    gu, gv, dc = vjp(gbar)
    n = z.shape[0]
    dz = _sorted_segsum(gu, u, u_pb, u_pc, u_pf, n)
    dz = dz + _sorted_segsum(gv[v_perm], v_sorted, v_pb, v_pc, v_pf, n)
    return (dz.astype(z.dtype), dc, None, None, None, None, None, None,
            None, None, None, None)


pair_sqdist_planned.defvjp(_pair_planned_fwd, _pair_planned_bwd)
