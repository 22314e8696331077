"""Block-CSR segment-sum Pallas kernel — scatter as MXU matmul.

XLA's scatter-add lowers to a serialized row-by-row update on TPU: at
ogbn-arxiv scale (2.4 M × 128 f32 edge values into 169 k node rows) a
single ``segment_sum`` costs ~0.8–1.7 s on a v5e chip while the matching
gather is 28 ms.  Since every aggregation in this framework runs over a
**receiver-sorted** edge list (``data.graphs.prepare``), each node block's
incoming edges form a contiguous chunk range, and the scatter becomes a
sum of one-hot matmuls — MXU work instead of serialized stores
(SURVEY.md §7 hard-part #3; the reference's CUDA backend leans on
atomics for the same aggregation [INFERRED], which TPUs do not have):

    out[i·bn : (i+1)·bn]  =  Σ_chunks  onehot(recv_chunk − i·bn) @ vals_chunk

A host-side *plan* (``build_csr_plan``) flattens (node-block, edge-chunk)
pairs into one grid of work items so hub nodes cost exactly their edge
count — no per-block padding to the max degree.  Consecutive items share
an output block; Pallas keeps it resident in VMEM and the kernel zeroes
it on each block's first item (standard revisiting-reduction pattern).

Boundary chunks shared by two node blocks are loaded by both and masked
by the one-hot range test (local index outside [0, bn) matches nothing),
so total DMA is E + O(#blocks) chunk loads.  Measured at arxiv scale:
0.83 s (XLA sorted scatter) → ~8 ms.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hyperspace_tpu.kernels import _support as S

_BN = 128  # nodes per output block (sublane-tiled)
_BK = 512  # edges per chunk (grid-step amortization vs VMEM)


class CsrPlan(NamedTuple):
    """Work-item schedule for :func:`csr_segment_sum` (host-built, static).

    All three arrays have shape [T] (T = total work items); they ride
    through jit as ordinary int32 device arrays — only their *shape* is
    baked into the compiled program.
    """

    block: np.ndarray  # item -> output node-block index
    chunk: np.ndarray  # item -> edge-chunk index
    first: np.ndarray  # 1 iff item is the first of its node block


def build_csr_plan(
    receivers: np.ndarray, num_nodes: int, bn: int = _BN, bk: int = _BK
) -> CsrPlan:
    """Plan the (node-block × edge-chunk) work items for a sorted edge list.

    ``receivers`` must be ascending (``data.graphs.prepare`` guarantees
    it); padding edges point at ``num_nodes - 1`` and carry zero values,
    so they are inert wherever they land.
    """
    r = np.asarray(receivers)
    if len(r) > 1 and not np.all(np.diff(r) >= 0):
        raise ValueError("build_csr_plan requires receiver-sorted edges")
    e_pad = S.round_up(max(len(r), 1), bk)
    nb = -(-num_nodes // bn)
    nchunks = e_pad // bk
    # rowptr over *block* boundaries only — that is all the kernel needs
    starts = np.searchsorted(r, np.arange(nb) * bn, side="left")
    ends = np.searchsorted(r, np.minimum(np.arange(1, nb + 1) * bn, num_nodes),
                           side="left")
    # every block gets ≥1 item (so its output is zeroed), and all chunk
    # indices stay in [0, nchunks): an empty trailing block whose edge
    # range starts at exactly len(r) == e_pad must not index one past the
    # end, so clamp c0 first and apply the upper clamp last
    c0 = np.minimum(starts // bk, nchunks - 1)
    c1 = np.clip(-(-ends // bk), c0 + 1, nchunks)
    counts = c1 - c0
    t = int(counts.sum())
    block = np.repeat(np.arange(nb, dtype=np.int32), counts)
    chunk = (np.arange(t, dtype=np.int32)
             - np.repeat(np.cumsum(counts) - counts, counts)
             + np.repeat(c0, counts)).astype(np.int32)
    first = np.zeros(t, np.int32)
    first[np.cumsum(counts) - counts] = 1
    return CsrPlan(block=block, chunk=chunk.astype(np.int32), first=first)


def rows_for_device_plan(e: int) -> int:
    """``e`` rows as whole chunks, plus the one all-zero chunk that
    :func:`device_csr_plan`'s unused items point at."""
    return S.round_up(e, _BK) + _BK


def device_csr_plan(receivers: jax.Array, num_nodes: int) -> tuple:
    """:func:`build_csr_plan` on the device, for sorted receivers that
    are new on every step (the LP decoder's pair indices).

    The item count T depends on the data and a compiled program's shapes
    cannot, so the plan has the static length ``T_max = n_chunks +
    n_blocks`` (each node block adds at most one boundary chunk to the
    chunks, so T < T_max).  Its first T items are ``build_csr_plan``'s;
    the rest revisit the last block (``first = 0``: nothing is zeroed)
    with the LAST chunk, which must therefore hold zero rows only:
    ``len(receivers)`` is :func:`rows_for_device_plan` of the data's
    length, the rows behind the data zero, their ids ``num_nodes`` (so
    that a sort puts them last).
    """
    e, bn, bk = receivers.shape[0], _BN, _BK
    if e % bk:
        raise ValueError(f"device_csr_plan needs a whole number of {bk}-row "
                         f"chunks, got {e} rows")
    i32 = jnp.int32
    nchunks, nb = e // bk, -(-num_nodes // bn)
    bounds = jnp.searchsorted(
        receivers, jnp.minimum(jnp.arange(nb + 1, dtype=i32) * bn, num_nodes),
        side="left", method="scan_unrolled").astype(i32)
    starts, ends = bounds[:-1], bounds[1:]
    # the same clamps, in the same order, as build_csr_plan
    c0 = jnp.minimum(starts // bk, nchunks - 1)
    c1 = jnp.clip(-(-ends // bk), c0 + 1, nchunks)
    counts = c1 - c0
    offsets = jnp.cumsum(counts, dtype=i32) - counts  # strictly increasing
    t_max = nchunks + nb
    first = jnp.zeros(t_max, i32).at[offsets].set(
        1, indices_are_sorted=True, unique_indices=True)
    block = jnp.cumsum(first, dtype=i32) - 1  # stays nb - 1 past item T
    t = jnp.arange(t_max, dtype=i32)
    chunk = jnp.where(t < offsets[-1] + counts[-1],
                      t - offsets[block] + c0[block], nchunks - 1)
    return block, chunk, first


def selection_precision(dtype) -> jax.lax.Precision:
    """The MXU passes an EXACT one-hot selection of ``dtype`` values takes.

    A 0/1 one-hot times a value is the value or zero, so a selection
    matmul that accumulates in float32 is exact as soon as the value
    survives the MXU's rounding of its operand to bfloat16.  A bfloat16
    value does: ONE pass, ``DEFAULT``.  A float32 value needs its three
    bfloat16 pieces; ``HIGHEST`` is the spelling that keeps them, and it
    is six passes (it splits the one-hot too, whose other two pieces are
    zero), where ``DEFAULT`` would cost ~1e-3 relative.
    ``kernels/cluster.py`` follows the same rule ("f32 inputs use
    HIGHEST").  What it is worth: a work item of ``csr_segment_sum`` is
    four ``[128, 128] @ [128, dp]`` products; at dp = 128 six passes
    are 4 x 6 x 2 x 128^3 = 101 MFLOP, 0.51 us of a v5e's 197 TFLOP/s,
    one pass 0.085 us, and the item went 0.69 -> 0.43 us with the
    same sums, bit for bit (PERF.md §6, PR 31).
    """
    return (jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)


def _body(bn: int, precision):
    def body(blk_ref, chk_ref, first_ref, recv_ref, vals_ref, o_ref):
        t = pl.program_id(0)
        b = blk_ref[t]

        @pl.when(first_ref[t] == 1)
        def _():
            o_ref[:] = jnp.zeros_like(o_ref)

        recv = recv_ref[0]                       # [bk//128, 128] int32
        local = recv - b * bn
        acc = jnp.zeros_like(o_ref[:], jnp.float32)
        rows = jax.lax.broadcasted_iota(jnp.int32, (bn, 128), 0)
        # 128-edge sub-chunks: one-hot [bn, 128] @ vals [128, dp] on the MXU
        for j in range(recv.shape[0]):
            oh = (rows == local[j : j + 1, :]).astype(jnp.float32)
            vals = vals_ref[j * 128 : (j + 1) * 128, :].astype(jnp.float32)
            acc += jnp.dot(oh, vals, preferred_element_type=jnp.float32,
                           precision=precision)
        o_ref[:] += acc

    return body


def _pallas_csr(values, receivers, plan, num_segments, interpret, precision):
    """The kernel call: float32 ``[num_segments, F]`` sums of ``values``'
    rows, the selection matmul at ``precision``."""
    e, f = values.shape
    bn, bk = _BN, _BK
    dp = S.round_up(f, 128)
    e_pad = S.round_up(e, bk)
    vals = S.pad_axis(S.pad_axis(values, -1, 128), 0, bk)
    recv2d = S.pad_axis(receivers, 0, bk).reshape(e_pad // bk, bk // 128, 128)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(plan[0].shape[0],),
        in_specs=[
            pl.BlockSpec((1, bk // 128, 128),
                         lambda t, blk, chk, first: (chk[t], 0, 0)),
            pl.BlockSpec((bk, dp), lambda t, blk, chk, first: (chk[t], 0)),
        ],
        out_specs=pl.BlockSpec((bn, dp), lambda t, blk, chk, first: (blk[t], 0)),
    )
    out = pl.pallas_call(
        _body(bn, precision),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S.round_up(num_segments, bn), dp),
                                       jnp.float32),
        name="csr_segment_sum",
        interpret=interpret,
    )(*plan, recv2d, vals)
    return out[:num_segments, :f]


def csr_segment_sum(
    values: jax.Array,     # [E, F] edge values (zero on padding edges)
    receivers: jax.Array,  # [E] int32, sorted ascending
    plan: tuple,           # CsrPlan as device arrays (block, chunk, first)
    num_segments: int,
) -> jax.Array:
    """``segment_sum(values, receivers)`` via block-CSR one-hot matmuls.

    Twin/oracle: ``jax.ops.segment_sum(..., indices_are_sorted=True)``.
    The plan must have been built from the same (sorted) receivers with
    :func:`build_csr_plan`.
    """
    m = S.mode()
    if m == "xla":
        # same accumulate-in-≥f32 semantics as the kernel (f64 stays f64)
        acc_dt = jnp.promote_types(values.dtype, jnp.float32)
        acc = jax.ops.segment_sum(values.astype(acc_dt), receivers,
                                  num_segments, indices_are_sorted=True)
        return acc.astype(values.dtype)
    out = _pallas_csr(values, receivers, tuple(plan), num_segments,
                      S.interpret_flag(m), selection_precision(values.dtype))
    return out.astype(values.dtype)


def _body_t(bn: int, precision):
    def body(blk_ref, chk_ref, first_ref, recv_ref, vals_ref, o_ref):
        t = pl.program_id(0)
        b = blk_ref[t]

        @pl.when(first_ref[t] == 1)
        def _():
            o_ref[:] = jnp.zeros_like(o_ref)

        recv = recv_ref[0]                       # [bk//128, 128] int32
        local = recv - b * bn
        acc = jnp.zeros_like(o_ref[:], jnp.float32)        # [F, bn]
        rows = jax.lax.broadcasted_iota(jnp.int32, (bn, 128), 0)
        for j in range(recv.shape[0]):
            oh = (rows == local[j : j + 1, :]).astype(jnp.float32)
            vals = vals_ref[:, j * 128 : (j + 1) * 128].astype(jnp.float32)
            # [F, 128 edges] x [bn, 128 edges], contracted over the edges
            acc += jax.lax.dot_general(
                vals, oh, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision)
        o_ref[:] += acc

    return body


def pair_scatter_sum(
    values_t: jax.Array,   # [F, E] edge values, one edge a column
    receivers: jax.Array,  # [E] int32, sorted, laid out for device_csr_plan
    num_segments: int,
) -> jax.Array:
    """:func:`csr_segment_sum` of narrow rows handed over TRANSPOSED
    (the LP decoder's ``[E, 33]`` cotangent rows); ``[F, num_segments]``
    float32.  XLA keeps such an array with E on the lanes (33 of 128
    lanes would idle), which is this layout for free, while the
    row-major ``[E, 128]`` that ``csr_segment_sum`` reads costs a
    transposing copy and a pad, 2 + 3 ms at 3.8 M rows (PERF.md §6,
    PR 27).  The same one-hot matmuls, operands swapped, under a plan
    built on the device (the receivers are new on every step); a call
    name of its own, so that a trace tells the decoder's call from the
    aggregation's.  Twin: sorted ``segment_sum`` in float32."""
    f, e = values_t.shape
    m = S.mode()
    if m == "xla":
        acc_dt = jnp.promote_types(values_t.dtype, jnp.float32)
        return jax.ops.segment_sum(values_t.T.astype(acc_dt), receivers,
                                   num_segments, indices_are_sorted=True).T
    bn, bk = _BN, _BK
    if e % bk:
        raise ValueError(f"pair_scatter_sum needs whole {bk}-edge chunks, "
                         f"got {e} edges")
    plan = device_csr_plan(receivers, num_segments)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(plan[0].shape[0],),
        in_specs=[
            pl.BlockSpec((1, bk // 128, 128),
                         lambda t, blk, chk, first: (chk[t], 0, 0)),
            pl.BlockSpec((f, bk), lambda t, blk, chk, first: (0, chk[t])),
        ],
        out_specs=pl.BlockSpec((f, bn), lambda t, blk, chk, first: (0, blk[t])),
    )
    out = pl.pallas_call(
        _body_t(bn, selection_precision(values_t.dtype)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((f, S.round_up(num_segments, bn)),
                                       jnp.float32),
        name="pair_scatter_sum",
        interpret=S.interpret_flag(m),
    )(*plan, receivers.reshape(e // bk, bk // 128, 128), values_t)
    return out[:, :num_segments]


def rows_to_columns(x: jax.Array) -> jax.Array:
    """``x.T`` for a long narrow row-major ``x`` ``[R, F]`` (R a
    multiple of 512), as ONE pass.  XLA's gather writes ``[R, 33]`` rows
    row-major and its elementwise consumers want R on the lanes; left to
    itself it slices the rows row-major and then copies every slice,
    19 ms for the LP decoder's 7.5 M re-gathered rows (PERF.md §6,
    PR 27).  A Pallas
    call's operand is row-major and its result what the kernel writes,
    so the transposition happens here and nowhere else."""
    r, f = x.shape
    m = S.mode()
    if m == "xla":
        return x.T
    if r % 512:
        raise ValueError(f"rows_to_columns needs whole 512-row blocks, "
                         f"got {r} rows")
    block_rows = next(b for b in (2048, 1024, 512) if r % b == 0)

    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...].T

    return pl.pallas_call(
        body,
        grid=(r // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, f), lambda t: (t, 0))],
        out_specs=pl.BlockSpec((f, block_rows), lambda t: (0, t)),
        out_shape=jax.ShapeDtypeStruct((f, r), x.dtype),
        name="rows_to_columns",
        interpret=S.interpret_flag(m),
    )(x)


# --- scalar (per-edge) segment reductions -------------------------------------


NEG_FILL = -3.0e38  # f32-safe -inf stand-in (finite so max-accumulate stays exact;
# nn.gcn imports it for the matching empty-segment threshold)


def _body_1d(bn: int, op: str):
    init = 0.0 if op == "sum" else NEG_FILL

    def body(blk_ref, chk_ref, first_ref, recv_ref, vals_ref, o_ref):
        t = pl.program_id(0)
        b = blk_ref[t]

        @pl.when(first_ref[t] == 1)
        def _():
            o_ref[:] = jnp.full_like(o_ref, init)

        recv = recv_ref[0]                        # [bk//128, 128] int32
        vals = vals_ref[0].astype(jnp.float32)    # [bk//128, 128]
        local = recv - b * bn
        rows = jax.lax.broadcasted_iota(jnp.int32, (bn, 128), 0)
        acc = o_ref[:]
        # lane-partial accumulation: each 128-edge sub-chunk contributes a
        # [bn, 128] select; the per-row combine over lanes happens once,
        # outside the kernel (an XLA row reduction of [n_pad, 128])
        for j in range(recv.shape[0]):
            sel = jnp.where(rows == local[j : j + 1, :],
                            jnp.broadcast_to(vals[j : j + 1, :], (bn, 128)),
                            init)
            acc = acc + sel if op == "sum" else jnp.maximum(acc, sel)
        o_ref[:] = acc

    return body


def csr_segment_reduce_1d(
    values: jax.Array,     # [E] per-edge scalars (0 / -inf-safe on padding)
    receivers: jax.Array,  # [E] int32, sorted ascending
    plan: tuple,           # CsrPlan device arrays (block, chunk, first)
    num_segments: int,
    op: str = "sum",
) -> jax.Array:
    """Per-segment scalar ``sum`` or ``max`` via the block-CSR plan.

    The matmul trick doesn't apply to scalars (and padding a [E] column to
    128 lanes would 128x the HBM traffic), so the kernel keeps a [bn, 128]
    lane-partial accumulator per node block and the final 128-lane combine
    runs as one XLA row-reduction.  Replaces XLA's serialized scalar
    scatter (~0.8 s at 2.4 M edges) in segment-softmax attention.
    """
    assert op in ("sum", "max"), op
    m = S.mode()
    if m == "xla":
        if op == "sum":
            # match the Pallas path: accumulate in ≥f32 (summing bf16
            # terms directly drops contributions past ~256×), then cast
            # back to the input dtype like the kernel's epilogue does
            acc = jax.ops.segment_sum(
                values.astype(jnp.promote_types(values.dtype, jnp.float32)),
                receivers, num_segments, indices_are_sorted=True)
            return acc.astype(values.dtype)
        return jax.ops.segment_max(values, receivers, num_segments,
                                   indices_are_sorted=True)
    e = values.shape[0]
    bn, bk = _BN, _BK
    e_pad = S.round_up(e, bk)
    fill = 0.0 if op == "sum" else NEG_FILL
    v = jnp.pad(values.astype(jnp.float32), (0, e_pad - e),
                constant_values=fill)
    v2d = v.reshape(e_pad // bk, bk // 128, 128)
    recv2d = S.pad_axis(receivers, 0, bk).reshape(e_pad // bk, bk // 128, 128)
    t = plan[0].shape[0]
    n_pad = S.round_up(num_segments, bn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, bk // 128, 128),
                         lambda t, blk, chk, first: (chk[t], 0, 0)),
            pl.BlockSpec((1, bk // 128, 128),
                         lambda t, blk, chk, first: (chk[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, 128),
                               lambda t, blk, chk, first: (blk[t], 0)),
    )
    out = pl.pallas_call(
        _body_1d(bn, op),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, 128), jnp.float32),
        name="csr_segment_reduce_1d",
        interpret=S.interpret_flag(m),
    )(*tuple(plan), recv2d, v2d)
    red = jnp.sum(out, axis=-1) if op == "sum" else jnp.max(out, axis=-1)
    return red[:num_segments].astype(values.dtype)


def _first_visit_of_chunk(pc: jax.Array) -> jax.Array:
    """1 on the plan item that visits its chunk first: chunk indices are
    non-decreasing in item order (a block-major plan), so that is where
    the value changes."""
    return jnp.concatenate([jnp.ones((1,), jnp.int32),
                            (pc[1:] > pc[:-1]).astype(jnp.int32)])


def _body_expand_1d(bn: int):
    def body(blk_ref, chk_ref, firstc_ref, recv_ref, vals_ref, o_ref):
        t = pl.program_id(0)
        b = blk_ref[t]

        @pl.when(firstc_ref[t] == 1)
        def _():
            o_ref[:] = jnp.zeros_like(o_ref)

        recv = recv_ref[0]                        # [bk//128, 128] int32
        local = recv - b * bn
        # the block's bn values arrive with the nodes on the lanes; the
        # select wants them down the sublanes, the edges on the lanes
        col = jnp.broadcast_to(vals_ref[0], (128, bn)).T  # row n = vals[n]
        rows = jax.lax.broadcasted_iota(jnp.int32, (bn, 128), 0)
        for j in range(recv.shape[0]):
            sel = jnp.where(rows == local[j : j + 1, :], col, 0.0)
            # one row of a lane holds the value and the others 0: the sum
            # is the selection, exactly.  A chunk that straddles blocks is
            # visited once per block; foreign lanes add 0
            o_ref[0, j, :] += jnp.sum(sel, axis=0)

    return body


def csr_segment_expand_1d(
    values: jax.Array,     # [N] per-node scalars
    receivers: jax.Array,  # [E] int32, sorted ascending
    plan: tuple,           # CsrPlan device arrays (block, chunk, first)
    num_segments: int,
) -> jax.Array:
    """``values[receivers]``, the transpose of
    :func:`csr_segment_reduce_1d`, on the same plan: each work item holds
    its node block's 128 values resident and its chunk's 512 receivers,
    and selects by the ``rows == local`` comparison the reductions use.
    XLA's 1-D gather moves such a scalar at 7 ns an edge on v5e; this
    walk moves it at the rate of the scalar reductions (PERF.md §6,
    PR 29).  A selection, no arithmetic: the result is the gather's,
    bit for bit (a ``-0.0`` comes out ``0.0``).  Twin: the gather."""
    m = S.mode()
    if m == "xla":
        return values[receivers]
    e = receivers.shape[0]
    bn, bk = _BN, _BK
    e_pad = S.round_up(e, bk)
    n_pad = S.round_up(num_segments, bn)
    v = S.pad_axis(values.astype(jnp.float32), 0, bn).reshape(
        n_pad // bn, 1, bn)
    recv2d = S.pad_axis(receivers, 0, bk).reshape(e_pad // bk, bk // 128, 128)
    pb, pc, _ = tuple(plan)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(pb.shape[0],),
        in_specs=[
            pl.BlockSpec((1, bk // 128, 128),
                         lambda t, blk, chk, fc: (chk[t], 0, 0)),
            pl.BlockSpec((1, 1, bn), lambda t, blk, chk, fc: (blk[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bk // 128, 128),
                               lambda t, blk, chk, fc: (chk[t], 0, 0)),
    )
    out = pl.pallas_call(
        _body_expand_1d(bn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e_pad // bk, bk // 128, 128),
                                       jnp.float32),
        name="csr_segment_expand_1d",
        interpret=S.interpret_flag(m),
    )(pb, pc, _first_visit_of_chunk(pc), recv2d, v)
    return out.reshape(e_pad)[:e].astype(values.dtype)


# --- fused attention backward over edges ---------------------------------------


def _body_att_bwd(bn: int, bound: float, negative_slope: float):
    def body(blk_ref, chk_ref, first_ref, firstc_ref, recv_ref, dn_ref,
             h1_ref, w_ref, lm_ref, dpre_ref, dar_ref):
        t = pl.program_id(0)
        b = blk_ref[t]

        @pl.when(first_ref[t] == 1)
        def _():
            dar_ref[:] = jnp.zeros_like(dar_ref)

        @pl.when(firstc_ref[t] == 1)
        def _():
            dpre_ref[:] = jnp.zeros_like(dpre_ref)

        recv = recv_ref[0]                       # [bk//128, 128] int32
        w = w_ref[0].astype(jnp.float32)
        lm = lm_ref[0].astype(jnp.float32)
        dn = dn_ref[:].astype(jnp.float32)       # [bn, dp1] (d_num | d_den)
        local = recv - b * bn
        rows = jax.lax.broadcasted_iota(jnp.int32, (bn, 128), 0)
        dar_acc = dar_ref[:]
        for j in range(recv.shape[0]):
            oh = (rows == local[j : j + 1, :]).astype(jnp.float32)
            # per-edge pick of this block's (d_num | d_den) rows: ohT @ dn
            dn_pick = jax.lax.dot_general(      # [128, dp1], MXU
                oh, dn, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            h1 = h1_ref[j * 128 : (j + 1) * 128, :].astype(jnp.float32)
            # dw = <d_num[r], h[s]> + d_den[r]: h1 carries a ones column
            # in the d_den lane, so one row-dot covers both terms
            dw = jnp.sum(dn_pick * h1, axis=-1)            # [128]
            leaky_g = jnp.where(lm[j] >= 0.0, 1.0, negative_slope)
            dpre_j = dw * w[j] * (1.0 - (lm[j] / bound) ** 2) * leaky_g
            # foreign lanes (another block's edges in a boundary chunk)
            # have all-zero one-hots → dw = 0 → dpre_j = 0: the owning
            # block's visit supplies the value, accumulation is exact
            dpre_ref[0, j, :] += dpre_j
            dar_acc = dar_acc + jnp.where(rows == local[j : j + 1, :],
                                          jnp.broadcast_to(
                                              dpre_j[None, :], (bn, 128)),
                                          0.0)
        dar_ref[:] = dar_acc

    return body


def csr_att_bwd_edges(
    dn_ext: jax.Array,     # [N, F+1] (d_num | d_den) node rows, f32
    h1: jax.Array,         # [E, F+1] residual sender rows | ones column
    w: jax.Array,          # [E] forward softmax weights (0 on padding)
    lm: jax.Array,         # [E] bounded logits
    receivers: jax.Array,  # [E] int32 sorted
    plan: tuple,           # CsrPlan device arrays
    num_segments: int,
    bound: float,
    negative_slope: float,
) -> tuple[jax.Array, jax.Array]:
    """Fused attention-backward edge pass (nn/scatter.att_aggregate_planned).

    One walk of the CSR plan computes, per edge,
    ``dw = <d_num[r], h[s]> + d_den[r]`` (the receiver-side rows are
    picked from the VMEM-resident node block by one-hot matmul — no [E]
    gather of d_num), chains it through the bounded-logit softmax weight
    ``w = exp(B·tanh(leaky(pre)/B))`` to ``dpre``, writes the edge-
    aligned ``dpre`` stream, AND accumulates the receiver-side score
    gradient ``d_alpha_r = segsum(dpre)`` in the same pass — replacing a
    sorted [E, F] gather, an [E, F] elementwise row-dot pass, an [E]
    elementwise chain, and a scalar CSR reduction (4 HBM passes → 1).
    Twin/oracle: the unfused chain (tests/nn/test_scatter.py).
    """
    m = S.mode()
    f1 = dn_ext.shape[-1]
    if m == "xla":
        dn_r = dn_ext[receivers]
        dw = jnp.sum(dn_r * h1.astype(jnp.float32), axis=-1)
        leaky_g = jnp.where(lm >= 0.0, 1.0, negative_slope)
        dpre = (dw * w.astype(jnp.float32)
                * (1.0 - (lm / bound) ** 2) * leaky_g)
        dar = jax.ops.segment_sum(dpre, receivers, num_segments,
                                  indices_are_sorted=True)
        return dpre, dar
    e = w.shape[0]
    bn, bk = _BN, _BK
    e_pad = S.round_up(e, bk)
    dp1 = S.round_up(f1, 128)
    dn_p = S.pad_axis(S.pad_axis(dn_ext.astype(jnp.float32), -1, 128), 0, bn)
    h1_p = S.pad_axis(S.pad_axis(h1, -1, 128), 0, bk)
    w2d = jnp.pad(w.astype(jnp.float32), (0, e_pad - e)).reshape(
        e_pad // bk, bk // 128, 128)
    lm2d = jnp.pad(lm.astype(jnp.float32), (0, e_pad - e)).reshape(
        e_pad // bk, bk // 128, 128)
    recv2d = S.pad_axis(receivers, 0, bk).reshape(e_pad // bk, bk // 128, 128)
    pb, pc, pf = tuple(plan)
    fc = _first_visit_of_chunk(pc)
    t = pb.shape[0]
    n_pad = S.round_up(num_segments, bn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, bk // 128, 128),
                         lambda t, blk, chk, first, fc: (chk[t], 0, 0)),
            pl.BlockSpec((bn, dp1),
                         lambda t, blk, chk, first, fc: (blk[t], 0)),
            pl.BlockSpec((bk, dp1),
                         lambda t, blk, chk, first, fc: (chk[t], 0)),
            pl.BlockSpec((1, bk // 128, 128),
                         lambda t, blk, chk, first, fc: (chk[t], 0, 0)),
            pl.BlockSpec((1, bk // 128, 128),
                         lambda t, blk, chk, first, fc: (chk[t], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk // 128, 128),
                         lambda t, blk, chk, first, fc: (chk[t], 0, 0)),
            pl.BlockSpec((bn, 128),
                         lambda t, blk, chk, first, fc: (blk[t], 0)),
        ],
    )
    dpre2d, dar = pl.pallas_call(
        _body_att_bwd(bn, bound, negative_slope),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((e_pad // bk, bk // 128, 128), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 128), jnp.float32),
        ],
        name="csr_att_bwd_edges",
        interpret=S.interpret_flag(m),
    )(pb, pc, pf, fc, recv2d, dn_p, h1_p, w2d, lm2d)
    return (dpre2d.reshape(e_pad)[:e],
            jnp.sum(dar, axis=-1)[:num_segments])
