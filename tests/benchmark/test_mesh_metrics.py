"""The four-chip cell's per-layer metrics on hand-built reductions: a
start/done pair is one collective, the mean is over the devices, the
skew is the spread of the time outside collectives, the step's split by
scope adds up to the device's busy time, the rows come from the
program's gauges, and the exchange's share of the interconnect's peak
reads 100 on a trace made to take exactly the peak's time (and less
when a transfer hides behind compute) — never more.  Each through
``run.py``'s own loader, from the metric's files alone; and every one of
them stays silent, without raising, on what a program from before this
cell gives."""

import json
import os
import types

import pytest

from benchmark import costs_mesh, mesh_reduce
from benchmark import run as bench_run
from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.peaks import device_peaks
from tests.benchmark.helpers import REPO
from tests.benchmark.test_scope_reduce import ev, line, plane

PEAKS = device_peaks("TPU v5 lite")
J = "jit(<unknown>)/"
HALO = "encoder/conv0/aggregate/shard_map/halo_exchange/all_gather"
FWD, BWD = J + "jvp(M)/", J + "transpose(jvp(M))/"
AG_START = ("%all-gather-start.3 = (bf16[368256,128]{1,0}, bf16[736512,128]"
            "{1,0}) all-gather-start(bf16[368256,128]{1,0} %convert.7), "
            "channel_id=5, replica_groups={{0,2},{1,3}}, dimensions={0}")
AG_DONE = ("%all-gather-done.3 = bf16[736512,128]{1,0} all-gather-done("
           "(bf16[368256,128]{1,0}, bf16[736512,128]{1,0}) "
           "%all-gather-start.3)")
ALL_REDUCE = ("%all-reduce.1 = f32[736512,33]{1,0} all-reduce(f32[736512,33]"
              "{1,0} %fusion.8), channel_id=9, to_apply=%add")
FUSION = ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %all-gather-done.3), "
          "kind=kLoop, calls=%fused_computation")
CELL = "hgcn_magcite_lp.mesh4"
NEW = ["collective_time", "halo_exchange_time", "halo_roofline",
       "shard_skew_time", "halo_rows_per_step", "mesh_encoder_time",
       "mesh_pair_dist_time", "mesh_kernel_time", "mesh_agg_glue_time",
       "mesh_pair_dist_bwd_time", "mesh_loss_optimizer_time",
       "mesh_unattributed_time"]


def _read(metric, red, facts):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"] if m["name"] == metric]
    assert entry and entry[0]["workloads"] == [CELL]
    out = bench_run.read_layer_metrics(REPO, entry, red, facts, PEAKS)
    return out[metric]["value"] if metric in out else None


def _device(name, ops):
    return tr.Device(name, sorted(ops), [])


def test_a_start_done_pair_is_one_collective_counted_once():
    ops = [(100.0, 110.0, AG_START), (110.0, 400.0, FUSION),
           (400.0, 460.0, AG_DONE), (500.0, 530.0, ALL_REDUCE)]
    assert mesh_reduce.collective_kind(AG_START) == (
        "all-gather", "start", "%all-gather-start.3")
    assert mesh_reduce.collective_kind(AG_DONE)[:2] == ("all-gather", "done")
    assert mesh_reduce.collective_kind(ALL_REDUCE)[:2] == (
        "all-reduce", "sync")
    # a fusion that reads a collective's result is none itself
    assert mesh_reduce.collective_kind(FUSION) is None
    assert mesh_reduce.pairs(ops) == [(100.0, 460.0, "all-gather"),
                                      (500.0, 530.0, "all-reduce")]
    assert mesh_reduce.exposed_ns(ops) == 10 + 60 + 30
    assert mesh_reduce.in_flight_ns(ops) == 360 + 30
    # a done whose start lies before the window counts from its own start
    assert mesh_reduce.pairs(ops[2:]) == [(400.0, 460.0, "all-gather"),
                                          (500.0, 530.0, "all-reduce")]


def test_collective_time_is_the_mean_over_devices_and_skew_the_spread():
    busy = [(0.0, 1000.0, FUSION)]
    red = tr.Reduced(0.0, 4000.0, [
        _device("/device:TPU:0", busy + [(1000.0, 1010.0, AG_START),
                                         (1400.0, 1460.0, AG_DONE)]),
        _device("/device:TPU:1", busy + [(1000.0, 1010.0, AG_START),
                                         (1400.0, 1760.0, AG_DONE),
                                         (2000.0, 2030.0, ALL_REDUCE)])], [])
    facts = {"steps_traced": 2}
    # (70 + 400) / 2 devices / 2 steps, in ms
    assert _read("collective_time", red, facts) == pytest.approx(
        117.5 * 1e-6)
    # both devices read 1000 ns outside collectives: no skew, though
    # their busy times differ by the longer wait
    assert _read("shard_skew_time", red, facts) == pytest.approx(0.0)
    red.devices[1].ops.append((3000.0, 3200.0, FUSION))
    assert _read("shard_skew_time", red, facts) == pytest.approx(
        200 / 2 * 1e-6)
    one = tr.Reduced(0.0, 4000.0, red.devices[:1], [])
    assert _read("shard_skew_time", one, facts) is None
    none = tr.Reduced(0.0, 4000.0, [_device("/device:TPU:0", busy)], [])
    assert _read("collective_time", none, facts) is None
    assert _read("shard_skew_time", tr.Reduced(
        0.0, 4000.0, none.devices * 2, []), facts) is None


def test_halo_rows_come_from_the_programs_gauge():
    facts = {"steps_traced": 2, "widths": [128, 128, 32],
             "halo_rows_moved_sum": 736_512.0}
    assert _read("halo_rows_per_step", None, facts) == 2 * 2 * 736_512
    assert _read("halo_rows_per_step", None, {"steps_traced": 2,
                                              "widths": [128, 128, 32]}) is None


def _halo_planes(transfer_ns, hidden_ns=0.0):
    """One device, one step: the exchange's all-gather as a pair whose
    done ends ``transfer_ns + hidden_ns`` after its start began, with a
    kernel between the two for ``hidden_ns`` of it."""
    t0 = 1100.0
    done_at = t0 + 10.0 + hidden_ns
    ops = [ev(AG_START, t0, 10.0, FWD + HALO),
           ev(AG_DONE, done_at, transfer_ns - 10.0, FWD + HALO)]
    if hidden_ns:
        ops.append(ev("%csr_segment_sum.1 = ...", t0 + 10.0, hidden_ns,
                      FWD + "encoder/conv0/aggregate/shard_map/kernel"))
    end = done_at + transfer_ns + 100.0
    return [plane("/host:CPU", [line("python3", [
        ev(tr.WINDOW_BEGIN, 1000.0, 1.0), ev(tr.WINDOW_END, end, 1.0)])]),
        plane("/device:TPU:0", [line("XLA Modules", []),
                                line("XLA Ops", ops)])]


@pytest.mark.parametrize("hidden_ns", [0.0, 5e5])
def test_halo_roofline_reads_100_at_the_ici_peak_and_never_more(
        hidden_ns, monkeypatch):
    rows, widths = 354_511.0, [128, 128, 32]
    facts = {"steps_traced": 1, "widths": widths, "msg_bytes": 2,
             "halo_rows_need_max": rows}
    assert costs_mesh.halo_bytes_step(rows, widths, 2) == (
        2 * rows * 160 * 2)
    least_ns = costs_mesh.halo_seconds(rows, widths, 2, PEAKS) * 1e9
    planes = _halo_planes(least_ns, hidden_ns)
    red, sc = tr.reduce_planes(planes), sr.scope_planes(planes)
    monkeypatch.setattr(sr, "load", lambda root, r: sc if r is red else None)
    share = _read("halo_roofline", red, facts)
    if hidden_ns:
        # the stretch between start and done is the transfer's too
        assert share == pytest.approx(
            100.0 * least_ns / (least_ns + hidden_ns))
    else:
        assert share == pytest.approx(100.0)
    assert share <= 100.0
    # the scope's own time leaves the hidden stretch out
    assert _read("halo_exchange_time", red, facts) == pytest.approx(
        least_ns * 1e-6)


def test_the_step_split_by_scope_adds_up(monkeypatch):
    """One device, one step, one operation of each kind: the glue is the
    aggregate scope less its kernel and less the exchange, the decoder's
    backward is the transposed half of pair_dist, and the rows
    negatives + encoder + pair_dist + loss/optimizer + unattributed are
    the device's busy time."""
    agg = "encoder/conv0/aggregate/shard_map/"
    kernel = ('%csr_segment_sum.1 = bf16[8,8]{1,0} custom-call(), '
              'custom_call_target="tpu_custom_call"')
    ops, t = [], 1100.0
    for name, dur, path in (
            ("%fusion.1 = ...", 3.0, FWD + "negatives/randint"),
            ("%fusion.2 = ...", 40.0, FWD + agg + "gather"),
            ("%fusion.3 = ...", 41.0, BWD + agg + "gather"),
            (kernel, 10.0, FWD + agg + "pallas_call"),
            (ALL_REDUCE, 30.0, FWD + HALO),
            ("%fusion.4 = ...", 5.0, FWD + "encoder/conv0/linear/dot"),
            ("%fusion.5 = ...", 7.0, FWD + "pair_dist/gather"),
            ("%fusion.6 = ...", 28.0, BWD + "pair_dist/scatter-add"),
            ("%fusion.7 = ...", 2.0, FWD + "loss/reduce_sum"),
            (ALL_REDUCE, 24.0, J + "optimizer/psum"),
            ("%copy.1 = ...", 1.5, "")):
        ops.append(ev(name, t, dur, path))
        t += dur + 1.0
    planes = [plane("/host:CPU", [line("python3", [
        ev(tr.WINDOW_BEGIN, 1000.0, 1.0), ev(tr.WINDOW_END, t + 10, 1.0)])]),
        plane("/device:TPU:0", [line("XLA Modules", []),
                                line("XLA Ops", ops)])]
    red, sc = tr.reduce_planes(planes), sr.scope_planes(planes)
    monkeypatch.setattr(sr, "load", lambda root, r: sc if r is red else None)
    facts = {"steps_traced": 1}
    ms = {m: _read(m, red, facts) * 1e6 for m in (
        "mesh_agg_glue_time", "mesh_pair_dist_bwd_time",
        "mesh_loss_optimizer_time", "mesh_unattributed_time",
        "mesh_encoder_time", "mesh_pair_dist_time", "mesh_kernel_time",
        "halo_exchange_time")}
    assert ms["mesh_agg_glue_time"] == pytest.approx(81.0)
    assert ms["mesh_kernel_time"] == pytest.approx(10.0)
    assert ms["halo_exchange_time"] == pytest.approx(30.0)
    assert ms["mesh_encoder_time"] == pytest.approx(81.0 + 10 + 30 + 5)
    assert ms["mesh_pair_dist_bwd_time"] == pytest.approx(28.0)
    assert ms["mesh_pair_dist_time"] == pytest.approx(35.0)
    assert ms["mesh_loss_optimizer_time"] == pytest.approx(26.0)
    assert ms["mesh_unattributed_time"] == pytest.approx(1.5)
    assert (3.0 + ms["mesh_encoder_time"] + ms["mesh_pair_dist_time"]
            + ms["mesh_loss_optimizer_time"] + ms["mesh_unattributed_time"]
            ) == pytest.approx(tr.busy_ns(red.devices[0]))


@pytest.mark.parametrize("metric", NEW)
def test_a_program_from_before_the_cell_gives_nothing_and_no_error(
        metric, monkeypatch):
    """No collective, no scope, no gauge: each reader returns nothing."""
    planes = [plane("/host:CPU", [line("python3", [
        ev(tr.WINDOW_BEGIN, 1000.0, 1.0), ev(tr.WINDOW_END, 2000.0, 1.0)])]),
        plane("/device:TPU:0", [line("XLA Modules", []), line("XLA Ops", [
            ev("%fusion.1 = ...", 1100.0, 50.0, J + "negatives/randint")])])]
    red, sc = tr.reduce_planes(planes), sr.scope_planes(planes)
    monkeypatch.setattr(sr, "load", lambda root, r: sc if r is red else None)
    assert _read(metric, red, {"steps_traced": 1, "widths": [8, 8],
                               "msg_bytes": 2}) is None
