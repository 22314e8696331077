"""The six per-kernel metrics on the existing ``op_time`` reader: each
data file's pattern takes the calls of its kernel alone, by the
instruction name that ``pl.pallas_call(name=...)`` gives the custom
call, and together they take what ``pallas_kernel_ms`` takes."""

import json
import os
import types

import pytest

from benchmark import run as bench_run
from tests.benchmark.helpers import REPO

KERNELS = ["cluster_aggregate", "csr_segment_sum", "csr_segment_reduce_1d",
           "cluster_att_fwd", "cluster_att_bwd", "csr_att_bwd_edges"]
TAIL = (' = f32[169344,128]{1,0:T(8,128)} custom-call(s32[4868]{0} %copy-done'
        '.101), custom_call_target="tpu_custom_call", frontend_attributes={}')


def _spec(metric):
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


def _red():
    """One device line: every kernel twice (first instruction unnumbered,
    as XLA leaves it), the parent's scope-named call, a sort custom call
    and a fusion whose operand is named after a kernel."""
    ops, t = [], 0.0
    for i, k in enumerate(KERNELS):
        for name in (f"%{k}", f"%{k}.{i + 4}"):
            ops.append((t, t + 10.0 * (i + 1), name + TAIL))
            t += 100.0
    ops.append((t, t + 7.0, "%conv0.9" + TAIL))
    ops.append((t + 100, t + 103.0, '%custom-call.27 = s32[8] custom-call('
                '...), custom_call_target="Sort"'))
    ops.append((t + 200, t + 205.0, "%fusion.3 = f32[8] fusion(f32[8] "
                "%csr_segment_sum.4), kind=kLoop"))
    dev = types.SimpleNamespace(name="/device:TPU:0", ops=ops, modules=[])
    return types.SimpleNamespace(devices=[dev], host_spans=[], t0=0.0,
                                 t1=t + 300, window_ns=t + 300)


def _read(metric):
    spec = _spec(metric)
    reader = bench_run.load_module(os.path.join(
        REPO, "benchmark", "readers", spec["reader"] + ".py"))
    return reader.read(_red(), {"steps_traced": 2}, None, spec)


@pytest.mark.parametrize("i,kernel", list(enumerate(KERNELS)))
def test_a_kernels_metric_takes_its_calls_alone(i, kernel):
    assert _spec(kernel + "_ms")["reader"] == "op_time"
    assert _read(kernel + "_ms") == pytest.approx(
        2 * 10.0 * (i + 1) / 2 * 1e-6)


def test_the_six_sum_to_the_named_share_of_pallas_kernel_ms():
    # pallas_kernel_ms also takes the parent's unnamed %conv0.9
    total = sum(_read(k + "_ms") for k in KERNELS)
    assert _read("pallas_kernel_ms") - total == pytest.approx(7.0 / 2 * 1e-6)
