"""Work a full-graph HGCN link-prediction step *requires*, from shapes.

Every function here counts what the algorithm needs and nothing of how a
program goes about it: no recomputation, no padding, no second read of an
operand, no layout passes.  A share of a peak computed from these counts
can therefore only read too low, never over 100%.

Shapes: ``n`` nodes, ``e`` directed message edges (both directions of
every training pair plus one self-loop a node), ``widths`` the feature
widths ``[f0, h1, ..., hL]`` in origin-tangent coordinates, ``pairs`` the
supervised pairs of a step (positives and negatives together).
"""

from __future__ import annotations

# elementwise work of one exp0 or log0 per coordinate (a norm, a scale)
_MAP_FLOPS_PER_COORD = 4


def linear_flops(n: int, widths) -> float:
    """u W + b for every layer, forward and backward.  The first layer
    needs no gradient for its input (features are data), the others
    need both the weight's and the input's."""
    total = 0.0
    for i, (a, b) in enumerate(zip(widths[:-1], widths[1:])):
        fwd = 2.0 * n * a * b
        total += fwd * (2 if i == 0 else 3)
    return total


def aggregate_flops(e: int, widths, use_att: bool) -> float:
    """Neighbour aggregation of every layer, forward and backward: one
    multiply-add an edge a coordinate each way; attention adds the
    weight's own gradient (an inner product an edge) and the per-edge
    logit, exponential and their derivatives."""
    total = 0.0
    for f in widths[1:]:
        total += 4.0 * e * f
        if use_att:
            total += 2.0 * e * f + 20.0 * e
    return total


def map_flops(n: int, widths) -> float:
    """exp0 of the input, then log0 and exp0 around every layer, forward
    and backward (the backward of an elementwise map costs as much
    again)."""
    coords = widths[0] + sum(widths[:-1]) + sum(widths[1:])
    return 2.0 * _MAP_FLOPS_PER_COORD * n * coords


def decoder_flops(pairs: int, out_width: int) -> float:
    """Minkowski product of each pair's two points, forward, and its
    gradient to both points, backward."""
    return 6.0 * pairs * (out_width + 1)


def step_flops(n: int, e: int, widths, pairs: int, use_att: bool) -> float:
    return (linear_flops(n, widths) + aggregate_flops(e, widths, use_att)
            + map_flops(n, widths) + decoder_flops(pairs, widths[-1]))


def dense_step_flops(n: int, widths, pairs: int) -> float:
    """The same step evaluated densely (an n x n adjacency): the upper
    end a sparse count may never pass."""
    return step_flops(n, n * n, widths, pairs, True)


def aggregate_kernel_cost(n: int, e_block: int, e_rest: int, width: int,
                          msg_bytes: int) -> dict:
    """FLOPs and bytes the two aggregation kernels need for ONE pass
    over one layer (forward, or the backward's mirror image), each
    operand read once and each result written once.

    ``e_block`` edges go through the block kernel, which reads the node
    rows ([n, width] in the message type), an index pair and a weight an
    edge, and writes [n, width] float32.  ``e_rest`` edges go through
    the segment-sum kernel, which reads one message row and one receiver
    id an edge and writes [n, width] in the message type.
    """
    flops = 2.0 * e_block * width + 1.0 * e_rest * width
    byts = (n * width * msg_bytes + e_block * 12 + n * width * 4
            + e_rest * (width * msg_bytes + 4) + n * width * msg_bytes)
    return {"flops": flops, "bytes": float(byts)}


def aggregate_kernels_step_cost(n: int, e_block: int, e_rest: int, widths,
                                msg_bytes: int) -> dict:
    """Both kernels over every layer, forward and backward, one step."""
    flops = byts = 0.0
    for f in widths[1:]:
        c = aggregate_kernel_cost(n, e_block, e_rest, f, msg_bytes)
        flops += 2 * c["flops"]
        byts += 2 * c["bytes"]
    return {"flops": flops, "bytes": byts}


def roofline_seconds(cost: dict, peaks: dict) -> tuple:
    """(least seconds the chip could take, which bound binds)."""
    by_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return ((by_flops, "flops") if by_flops >= by_bytes
            else (by_bytes, "bytes"))
