"""The LP decoder's pair distances with a VJP that avoids XLA's scatter-add.

The decoder's backward sums millions of per-pair cotangent rows into the
[N, D] embedding.  XLA's scatter-add does that at about one row per
14 ns: at the ogbn-arxiv shape two scatters of ``bf16[1880610, 33]``
into ``bf16[169343, 33]`` cost 2 × 27.1 ms, with their index sorts 43%
of the mean arm's 136.5 ms step and the largest single cost of both arms
(PERF_LEDGER.jsonl, PR 26).  :func:`pair_sqdist` keeps the *math* of
``manifold.sqdist`` untouched (the backward re-runs its own VJP per
pair: clamps, custom gradients and the curvature cotangent included)
and changes only where the rows are computed and how they are summed:
it takes ANY pairs, new on every step, sorts the pair ends on the
device, recomputes the cotangent rows in node order from rows
re-gathered out of ``z`` (which XLA keeps in fast memory, ``S(1)``, as
long as it fits), plans on the device and sums with a block-CSR kernel.
Nothing about the step changes: same sampler, same pairs, same order,
same forward, the values and gradients of ``m.sqdist(z[u], z[v])``
(tests/nn/test_edge_dist.py).  ``train_step_lp`` (``cli.train``, both
one-chip benchmark cells) runs on it; the mesh steps keep XLA's
scatter-add (``models.hgcn._lp_step_impl``).
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

