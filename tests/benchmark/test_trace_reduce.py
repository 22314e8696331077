"""The trace reduction on a small hand-built trace: busy, idle, gaps,
sums by name, two devices, host spans on the trace's clock."""

import json
import types

import pytest

from benchmark import trace_reduce as tr


def ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


@pytest.fixture
def planes():
    host = plane("/host:CPU", [line("python", [
        ev(tr.WINDOW_BEGIN, 1000, 5), ev(tr.WINDOW_END, 2000, 5)])])
    dev0 = plane("/device:TPU:0", [
        line("XLA Modules", [ev("jit_step", 1100, 300),
                             ev("jit_step", 1500, 300)]),
        line("XLA Ops", [
            ev("fusion.1", 900, 150),        # starts before the window
            ev("pallas_a", 1100, 100), ev("fusion.2", 1150, 100),  # overlap
            ev("pallas_a", 1500, 200), ev("all-gather.3", 1700, 100),
            ev("fusion.1", 1950, 100)]),     # ends after the window
        line("Steps", [ev("ignored", 1000, 1000)])])
    dev1 = plane("/device:TPU:1", [
        line("XLA Modules", [ev("jit_step", 1100, 700)]),
        line("XLA Ops", [ev("pallas_a", 1100, 700)])])
    return [host, dev0, dev1]


def test_window_busy_and_idle(planes):
    red = tr.reduce_planes(planes)
    assert (red.t0, red.t1, red.window_ns) == (1000, 2000, 1000)
    d0, d1 = red.devices
    assert [d.name for d in red.devices] == ["/device:TPU:0",
                                             "/device:TPU:1"]
    # dev0: [1000,1050] + [1100,1250] + [1500,1800] + [1950,2000]
    assert tr.busy_ns(d0) == 50 + 150 + 300 + 50
    assert tr.busy_ns(d1) == 700
    assert tr.least_busy(red) is d0
    assert tr.gaps(d0.ops, red.t0, red.t1) == [
        (1050, 1100), (1250, 1500), (1800, 1950)]


def test_sums_by_name_and_between_programs(planes):
    red = tr.reduce_planes(planes)
    d0 = red.devices[0]
    assert tr.sum_by_patterns(d0.ops, ["pallas_*"]) == 300
    assert tr.sum_by_patterns(d0.ops, ["all-gather*", "all-reduce*"]) == 100
    assert tr.sum_by_patterns(d0.ops, ["nothing*"]) == 0
    assert tr.totals_by_name(d0.ops)["fusion.1"] == 100  # both clipped
    # outside any program: 1000-1100, 1400-1500, 1800-2000
    assert tr.between_programs_ns(d0, red.t0, red.t1) == 400


def test_host_spans_label_the_gaps(planes, tmp_path):
    # the anchor was taken at perf_counter 50.0 s; a "dispatch" span
    # covers trace time 1240..1510, a "metrics_flush" 1790..1960
    chrome = tmp_path / "spans.json"
    base_us = 50.0 * 1e6
    chrome.write_text(json.dumps({"traceEvents": [
        {"name": "dispatch", "ph": "X", "ts": base_us + 0.240, "dur": 0.270},
        {"name": "metrics_flush", "ph": "X", "ts": base_us + 0.790,
         "dur": 0.170}]}))
    red = tr.reduce_planes(planes, anchor_perf_s=50.0,
                           chrome_path=str(chrome))
    out = tr.breakdown(red, top=3)
    assert out["idle_gaps"][0] == ["dispatch", pytest.approx(250e-9)]
    assert out["idle_gaps"][1] == ["metrics_flush", pytest.approx(150e-9)]
    assert out["idle_gaps"][2][0] == "other"
    assert out["device_ops"][0] == ["pallas_a", pytest.approx(300e-9)]


def test_a_trace_without_the_window_is_refused(planes):
    with pytest.raises(ValueError):
        tr.reduce_planes(planes[1:])
