"""The LP decoder's pair distances with a VJP that avoids XLA's scatter-add.

The decoder's backward sums millions of per-pair cotangent rows into the
[N, D] embedding.  XLA's scatter-add does that at about one row per
14 ns: at the ogbn-arxiv shape two scatters of ``bf16[1880610, 33]``
into ``bf16[169343, 33]`` cost 2 × 27.1 ms, with their index sorts 43%
of the mean arm's 136.5 ms step and the largest single cost of both arms
(PERF_LEDGER.jsonl, PR 26).  :func:`pair_sqdist` keeps the *math* of
``manifold.sqdist`` untouched and changes only where the cotangent rows
are computed and how they are summed: it takes ANY pairs, new on every
step, lists each once for each end, sorts the listings by the end's node
on the device, computes the rows in node order from rows re-gathered out
of ``z`` (which XLA keeps in fast memory, ``S(1)``, as long as it fits),
plans on the device and sums with a block-CSR kernel.

Two backwards, chosen by what the manifold object states about its own
distance (no flag, no test of ``kind``).  A manifold with
``sqdist_of_dot`` (Lorentz: sqdist is a scalar map h of the Minkowski
dot alone, every clamp inside h) gets :func:`_dot_bwd`: autodiff of h on
``[P]`` arrays gives ONE scalar a pair and the curvature's cotangent,
the scalar rides the sort, and only the OTHER end's row is gathered:
2P rows (PR 34; 4P before).  Every other manifold gets
:func:`_generic_bwd` (PR 27): sqdist's own VJP re-run at both ends'
re-gathered points, clamps, custom gradients and the curvature cotangent
included.  Poincaré's distance is a map of three Euclidean dots and
admits the first form with a 1-D segment sum added; the generic path
goes when the last manifold has moved.

Nothing about the step changes: same sampler, same pairs, same order,
same forward, the values and gradients of ``m.sqdist(z[u], z[v])``
(tests/nn/test_edge_dist.py).  ``train_step_lp`` (``cli.train``, both
one-chip benchmark cells) runs on it; the mesh steps keep XLA's
scatter-add (``models.hgcn._lp_step_impl``).  The gauge
``decoder/pair_vjp_rows_per_step`` says which backward a run compiled.
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
from hyperspace_tpu.manifolds.lorentz import minkowski_dot, minkowski_flip
from hyperspace_tpu.telemetry import registry


def _manifold(kind: str, c):
    from hyperspace_tpu.nn.gcn import make_manifold

    return make_manifold(kind, c)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def pair_sqdist(
    z: jax.Array,   # [N, D] points on the manifold
    c,              # curvature (traced scalar; grads flow)
    u: jax.Array,   # [P] int32 in [0, N), any order, new on every step
    v: jax.Array,   # [P] int32, likewise
    kind: str = "lorentz",
) -> jax.Array:
    """sqdist(z[u_p], z[v_p]) whose VJP needs no plan from the host and
    no scatter-add: :func:`_dot_bwd` where the manifold states its
    squared distance as a scalar map of the Minkowski dot
    (``sqdist_of_dot``), :func:`_generic_bwd` for every other.

    The gain rests on XLA keeping ``z`` in fast memory for the backward's
    re-gather, which this code cannot observe.  Timed on a v5e against
    XLA's scatter-adds, forward + backward, ms a call, Lorentz ``z`` of
    33 bf16 lanes alone (scripts/sweep_pair_sqdist.py; PERF.md §6,
    PR 34; in brackets PR 27's backward, which gathered both ends)::

        rows N      pairs P     scatter-adds   this VJP
            2,708       8,976        0.45        0.47  (0.49)
           19,717      75,000        1.57        1.03
          169,343   1,880,610       75.9        27.9   (43.4)
          338,686   1,880,610       74.5        35.3
          368,256   1,880,610       74.2        28.7
          368,256   4,330,000      197.6        92.0
          677,372   1,880,610       88.8        79.1  (128.4)
        1,354,744   1,880,610      552.4       122.4  (185)
        2,449,029   1,880,610      614.4       200.4
        2,449,029   7,522,440     2450.0       782.0

    Level at the smallest graph, ahead at every other size timed, the
    one where PR 27's was behind (677,372 rows) included; 368,256 rows
    is a shard of the four-chip cell's table.  The generic backward
    stands where the brackets do.  Another width or manifold: time it
    before it trains on this."""
    return _pair_fwd(z, c, u, v, kind)[0]


def _pair_fwd(z, c, u, v, kind):
    m = _manifold(kind, c)
    if not hasattr(m, "sqdist_of_dot"):
        return m.sqdist(z[u], z[v]), (z, c, u, v, None)
    # m.sqdist's own two steps, the dot kept: the backward needs nothing
    # else of the pair's ends
    ip = minkowski_dot(z[u], z[v], keepdims=False)
    return m.sqdist_of_dot(ip), (z, c, u, v, ip)


def _pair_bwd(kind, res, gbar):
    z, c, u, v, ip = res
    dz, dc = (_generic_bwd(kind, z, c, u, v, gbar) if ip is None
              else _dot_bwd(kind, z, c, u, v, ip, gbar))
    return dz, dc, None, None


def _sort_by_end(n: int, ctr, oth, val):
    """The 2P listings ``(end's node, its payload, its value)`` as three
    1-D arrays sorted by the end's node, padded behind the data with the
    whole chunk of zero rows the device plan's unused items point at: id
    ``n`` sorts last, value 0 makes the row 0."""
    e = rows_for_device_plan(ctr.shape[0])
    pad = lambda x, fill: jnp.pad(x, (0, e - x.shape[0]),
                                  constant_values=fill)
    return jax.lax.sort((pad(ctr, n), pad(oth, 0), pad(val, 0)),
                        num_keys=1,
                        is_stable=False)  # equal ids only reorder a f32 sum


def _dot_bwd(kind, z, c, u, v, ip, gbar):
    """sqdist = h(⟨x,y⟩_L, c), so the rows autodiff computes are ∂/∂x =
    s·J·y and ∂/∂y = s·J·x with s = ḡ·∂h/∂ip ONE scalar a pair, and
    dz[n] = J·Σ s_p·z[other end] over the listings whose end is n: the
    two ends are symmetric and J (a negation of lane 0) commutes with
    the sum exactly.  Moving 3.8 M rows into node order costs XLA 10-16
    ns a row on a v5e whatever the op (gather, scatter, a sort that
    carries them: PERF.md §6, PR 27), but rows gathered from the SMALL
    ``z`` cost 1.6.  So nothing wide moves: s comes from autodiff of the
    scalar map on ``[P]`` arrays in pair order (every clamp and the
    curvature's cotangent are its own), each pair is listed twice, once
    for each end, (end's node, other end's node, s) is sorted by the
    end's node as three 1-D arrays, and ONLY the other end's row is
    gathered, ``rows_for_device_plan(2P)`` rows, and scaled.  The
    block-CSR kernel then sums the sorted rows
    (`kernels.segment.pair_scatter_sum`, planned on the device); float32
    accumulation, J, one cast."""
    n = z.shape[0]
    _, vjp = jax.vjp(lambda ip, c: _manifold(kind, c).sqdist_of_dot(ip),
                     ip, c)
    s, dc = vjp(gbar)
    ctr, oth, s = _sort_by_end(n, jnp.concatenate([u, v]),
                               jnp.concatenate([v, u]),
                               jnp.concatenate([s, s]))
    _note_vjp_rows(oth.shape[0])
    # scaled AFTER the transposition, lanes full: 27.8 ms a call against
    # 29.9 with the multiply in the gather's fusion (PERF.md §6, PR 34)
    rows_t = rows_to_columns(z[oth]) * s[None, :]
    dz = pair_scatter_sum(rows_t, ctr, n).T
    return minkowski_flip(dz).astype(z.dtype), dc


def _generic_bwd(kind, z, c, u, v, gbar):
    """For a manifold that states nothing about its distance: as
    :func:`_dot_bwd`, but the rows are computed in node order by
    sqdist's own VJP at BOTH ends' re-gathered points — the u end's row
    from its first argument, the v end's from its second, so they are
    the rows autodiff computes — which takes the listing's which-end bit
    through the sort and twice the rows out of ``z``."""
    n = z.shape[0]
    f = lambda a, b, c: _manifold(kind, c).sqdist(a, b)
    ctr, oth, gb = _sort_by_end(
        n, jnp.concatenate([u, v]),
        jnp.concatenate([2 * v, 2 * u + 1]),  # low bit: the v end
        jnp.concatenate([gbar, gbar]))
    e = ctr.shape[0]
    ctr_in, is_v, oth = jnp.minimum(ctr, n - 1), oth % 2 == 1, oth // 2
    _note_vjp_rows(2 * e)
    # ONE gather for both ends: XLA gathers at 1.6 ns a row only from a
    # table it keeps in fast memory, and of two copies of z one may not fit
    zz = rows_to_columns(z[jnp.concatenate([jnp.where(is_v, oth, ctr_in),
                                            jnp.where(is_v, ctr_in, oth)])])
    _, vjp = jax.vjp(f, zz[:, :e].T, zz[:, e:].T, c)
    g_u, g_v, dc_twice = vjp(gb)  # every pair is in the list twice
    rows = jnp.where(is_v[:, None], g_v, g_u)
    dz = pair_scatter_sum(rows.T, ctr, n).T
    return dz.astype(z.dtype), dc_twice / 2


def _note_vjp_rows(rows: int) -> None:
    """At trace time: the rows the compiled backward re-gathers from
    ``z`` a step, which says which of the two backwards a run is on."""
    registry.set_gauge("decoder/pair_vjp_rows_per_step", rows)  # hyperlint: disable=metric-unit-suffix — a ROW COUNT per step: the unit segment is mid-name, the suffix names the period


pair_sqdist.defvjp(_pair_fwd, _pair_bwd)
