"""costs.py against hand-worked numbers, and the property that required
work never passes what a dense evaluation of the same step does."""

import itertools

import pytest

from benchmark import costs
from benchmark.peaks import device_peaks


def test_hand_worked_tiny_shape():
    n, e, widths, pairs = 10, 30, [4, 3, 2], 8
    # linear: layer 0 fwd 2*10*4*3 = 240, x2 (no input gradient) = 480;
    #         layer 1 fwd 2*10*3*2 = 120, x3 = 360
    assert costs.linear_flops(n, widths) == 840
    # aggregation: 4*e*f over f in (3, 2) = 360 + 240
    assert costs.aggregate_flops(e, widths, False) == 600
    # attention adds 2*e*f + 20*e a layer: (180 + 600) + (120 + 600)
    assert costs.aggregate_flops(e, widths, True) == 600 + 1500
    # maps: coordinates 4 + (4 + 3) + (3 + 2) = 16; 2 * 4 * 10 * 16
    assert costs.map_flops(n, widths) == 1280
    assert costs.decoder_flops(pairs, 2) == 6 * 8 * 3
    assert costs.step_flops(n, e, widths, pairs, False) == (
        840 + 600 + 1280 + 144)


def test_kernel_cost_counts_each_operand_once():
    c = costs.aggregate_kernel_cost(n=10, e_block=20, e_rest=5, width=4,
                                    msg_bytes=2)
    assert c["flops"] == 2 * 20 * 4 + 5 * 4
    assert c["bytes"] == (10 * 4 * 2 + 20 * 12 + 10 * 4 * 4
                          + 5 * (4 * 2 + 4) + 10 * 4 * 2)
    step = costs.aggregate_kernels_step_cost(10, 20, 5, [8, 4], 2)
    assert step == {"flops": 2 * c["flops"], "bytes": 2 * c["bytes"]}


@pytest.mark.parametrize("n,deg,widths,att", list(itertools.product(
    (64, 1000, 169_343), (1, 7, 40), ([128, 128, 32], [16, 8], [8, 64, 64, 4]),
    (False, True))))
def test_sparse_never_passes_dense(n, deg, widths, att):
    e = min(n * deg, n * n)
    pairs = 2 * e
    sparse = costs.step_flops(n, e, widths, pairs, att)
    assert 0 < sparse <= costs.dense_step_flops(n, widths, pairs)
    # bytes: while the graph is sparse enough that an edge's record
    # is cheaper than its share of a dense [n, n] float32 adjacency,
    # the kernels' required bytes stay under the dense evaluation's
    f_max = max(widths[1:])
    if e * (f_max * 2 + 12) <= n * n * 4:
        k = costs.aggregate_kernels_step_cost(n, e // 2, e - e // 2,
                                              widths, 2)
        dense = sum(2 * (n * n * 4 + n * f * (2 + 4 + 2))
                    for f in widths[1:])
        assert k["bytes"] <= dense


def test_roofline_names_its_bound():
    peaks = device_peaks("TPU v5 lite")
    s, bound = costs.roofline_seconds({"flops": 197e12, "bytes": 1.0}, peaks)
    assert (round(s, 6), bound) == (1.0, "flops")
    s, bound = costs.roofline_seconds({"flops": 1.0, "bytes": 819e9}, peaks)
    assert (round(s, 6), bound) == (1.0, "bytes")


def test_an_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        device_peaks("cpu")
