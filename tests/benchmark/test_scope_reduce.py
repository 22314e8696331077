"""``benchmark/scope_reduce.py`` and its two readers on a small
hand-built trace: a fusion under its root's path, ``exclude_ops``,
``pass``, the unattributed remainder, the step event's self time with
nested and back-to-back children, a trace without paths, another run's
trace, and the file reader against ``jax.profiler.ProfileData``."""

import json
import os
import types

import pytest

from benchmark import run as bench_run
from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr
from tests.benchmark.helpers import REPO

J = "jit(train_step_lp)/"
FWD, BWD = J + "jvp(HGCNLinkPred)/", J + "transpose(jvp(HGCNLinkPred))/"
CC = ' = f32[8,128] custom-call(...), custom_call_target="tpu_custom_call"'


def ev(name, start, dur, path=None, **stats):
    if path is not None:
        stats["tf_op"] = path + ":"
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


def device_ops():
    return [
        ev("%fusion.9 = ...", 900, 150, J + "negatives/randint"),  # clipped
        ev("%fusion.1 = ...", 1100, 40, FWD + "encoder/conv0/linear/dot"),
        # a fusion is named after its root: gather + mul under `gather`
        ev("%fusion.2 = ...", 1140, 60, FWD + "encoder/conv0/aggregate/gather"),
        ev("%csr_segment_sum.4" + CC, 1200, 100,
           FWD + "encoder/conv0/aggregate/csr_segment_sum/pallas_call"),
        ev("%fusion.3 = ...", 1300, 30, FWD + "pair_dist/gather"),
        ev("%fusion.4 = ...", 1330, 10, J + "jvp(loss)/reduce_sum"),
        ev("%fusion.5 = ...", 1340, 200, BWD + "pair_dist/scatter-add"),
        ev("%csr_segment_sum.5" + CC, 1540, 100,
           BWD + "encoder/conv0/aggregate/csr_segment_sum/pallas_call"),
        ev("%fusion.6 = ...", 1640, 50, BWD + "encoder/conv0/aggregate/mul"),
        ev("%fusion.7 = ...", 1690, 20, J + "optimizer/add"),
        ev("%copy.1 = ...", 1710, 7),                       # no path at all
        ev("%convert.1 = ...", 1717, 3, FWD + "convert_element_type"),
    ]


def host_lines():
    return [line("python3", [
        ev(tr.WINDOW_BEGIN, 1000, 5), ev(tr.WINDOW_END, 2000, 5),
        # step 0: dispatch with the runtime's call nested in it, then a
        # flush right behind it: 100 - (40 + 30) = 30 of its own
        ev("train", 1010, 100, step_num=0), ev("dispatch", 1020, 40),
        ev("PjitFunction(step)", 1025, 30), ev("metrics_flush", 1060, 30),
        # step 1: two children back to back and overlapping: 80 - 50
        ev("train", 1110, 80, step_num=1), ev("dispatch", 1115, 30),
        ev("metrics_flush", 1140, 25),
        ev("eval", 1300, 100)]),            # outside any step
        line("worker/7", [ev("dispatch", 1030, 500)])]  # another thread


@pytest.fixture
def planes():
    return [plane("/host:CPU", host_lines()),
            plane("/device:TPU:0", [
                line("XLA Modules", [ev("jit_step", 1100, 700)]),
                line("XLA Ops", device_ops())])]


def test_ops_keep_their_paths_and_the_window_clips(planes):
    sc = sr.scope_planes(planes)
    assert (sc.t0, sc.t1, sc.has_paths) == (1000, 2000, True)
    (dev,) = sc.devices
    assert dev.ops[0] == (1000, 1050, "%fusion.9 = ...",
                          J + "negatives/randint")
    assert [p for *_, p in dev.ops][-2:] == ["", FWD + "convert_element_type"]
    assert sr.path_ns(dev) == tr.union_ns(dev.ops) == 670
    assert set(sc.host) == {("/host:CPU", "python3"),
                            ("/host:CPU", "worker/7")}


SPECS = {
    "encoder": ({"patterns": ["*[/(]encoder[/)]*"]}, 350),
    "fusion_by_root_path": ({"patterns": ["*/aggregate/gather"]}, 60),
    "agg_glue": ({"patterns": ["*[/(]aggregate[/)]*"],
                  "exclude_ops": ["*custom_call_target=*"]}, 110),
    "pair_dist": ({"patterns": ["*[/(]pair_dist[/)]*"]}, 230),
    "pair_dist_bwd": ({"patterns": ["*[/(]pair_dist[/)]*"],
                       "pass": "bwd"}, 200),
    "encoder_fwd": ({"patterns": ["*[/(]encoder[/)]*"], "pass": "fwd"}, 200),
    "loss_outermost_in_jvp": ({"patterns": ["*[/(]loss[/)]*"]}, 10),
    "unattributed": ({"exclude_paths": [
        "*[/(]%s[/)]*" % s for s in ("negatives", "encoder", "pair_dist",
                                     "decoder", "loss", "optimizer")]}, 10),
    "nothing_matches": ({"patterns": ["*[/(]decoder[/)]*"]}, None),
}


@pytest.fixture
def reader_on(planes, monkeypatch):
    """The readers as ``run.py`` loads them, over the hand-built trace."""
    red = tr.reduce_planes(planes)
    sc = sr.scope_planes(planes)
    monkeypatch.setattr(sr, "load", lambda root, r: sc if r is red else None)

    def read(reader, spec, facts=None, red=red):
        mod = bench_run.load_module(os.path.join(
            REPO, "benchmark", "readers", reader + ".py"))
        return mod.read(red, {"steps_traced": 2} if facts is None else facts,
                        None, spec)

    return read


@pytest.mark.parametrize("case", sorted(SPECS))
def test_scope_time(reader_on, case):
    spec, want_ns = SPECS[case]
    got = reader_on("scope_time", spec)
    assert got == (None if want_ns is None
                   else pytest.approx(want_ns / 2 * 1e-6))


def test_the_scopes_and_the_remainder_sum_to_the_step(reader_on):
    parts = ["negatives", "encoder", "pair_dist", "loss", "optimizer"]
    total = sum(reader_on("scope_time", {"patterns": [f"*[/(]{p}[/)]*"]})
                for p in parts)
    total += reader_on("scope_time", SPECS["unattributed"][0])
    assert total == pytest.approx(670 / 2 * 1e-6)


def test_host_self_time_nested_and_back_to_back(reader_on, planes):
    sc = sr.scope_planes(planes)
    assert sr.self_ns(sc, "train") == (30 + 30, 2)
    assert reader_on("host_self_time", {"step_event": "train"}) == (
        pytest.approx(60 / 2 * 1e-6))
    # no such step event, no steps, no trace: nothing to read
    assert reader_on("host_self_time", {"step_event": "serve"}) is None
    assert reader_on("host_self_time", {"step_event": "train"},
                     facts={}) is None
    assert reader_on("host_self_time", {"step_event": "train"},
                     red=None) is None


def test_a_trace_without_paths_reads_none(monkeypatch):
    # the parent commit's view through ProfileData-like events: stats
    # absent or holding no path; and a CPU trace's `hlo_op` only
    bare = [types.SimpleNamespace(name="%fusion.1", start_ns=1100,
                                  duration_ns=100),
            ev("%fusion.2", 1200, 100, hlo_op="fusion.2", program_id=5)]
    planes = [plane("/host:CPU", [line("python3", [
        ev(tr.WINDOW_BEGIN, 1000, 5), ev(tr.WINDOW_END, 2000, 5)])]),
        plane("/device:TPU:0", [line("XLA Ops", bare)])]
    sc = sr.scope_planes(planes)
    assert not sc.has_paths and sr.path_ns(sc.devices[0]) == 200
    red = tr.reduce_planes(planes)
    monkeypatch.setattr(sr, "load", lambda root, r: sc)
    mod = bench_run.load_module(os.path.join(
        REPO, "benchmark", "readers", "scope_time.py"))
    assert mod.read(red, {"steps_traced": 1}, None, {}) is None
    host = bench_run.load_module(os.path.join(
        REPO, "benchmark", "readers", "host_self_time.py"))
    assert host.read(red, {"steps_traced": 1}, None,
                     {"step_event": "train"}) is None


def test_no_window_is_an_error_and_load_survives_it(tmp_path):
    with pytest.raises(ValueError):
        sr.scope_planes([plane("/device:TPU:0", [line("XLA Ops", [])])])
    assert sr.newest_xplane(str(tmp_path)) is None
    assert sr.load(str(tmp_path), types.SimpleNamespace(t0=0, t1=1)) is None
    assert sr.load(str(tmp_path), None) is None


# --- the file reader ---------------------------------------------------------


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(num, payload):
    """One field: an int is a varint, bytes are length-delimited."""
    if isinstance(payload, int):
        return _varint(num << 3) + _varint(payload & (2**64 - 1))
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _entry(key, msg):
    return _f(1, key) + _f(2, msg)


def test_read_xplane_merges_the_metadata_stats_into_the_event(tmp_path):
    """xplane.proto by hand: the path sits on the event's *metadata*
    (stat 7, a string), the device's own timing stat on the event."""
    stat_md = (_f(5, _entry(7, _f(1, 7) + _f(2, b"tf_op")))
               + _f(5, _entry(8, _f(1, 8) + _f(2, b"device_offset_ps")))
               + _f(5, _entry(9, _f(1, 9) + _f(2, b"step_num"))))
    op_md = (_f(1, 3) + _f(2, b"%fusion.2 = bf16[4] fusion(...)")
             + _f(5, _f(1, 7) + _f(5, (FWD + "pair_dist/gather:").encode())))
    begin = _f(1, 1) + _f(2, tr.WINDOW_BEGIN.encode())
    end = _f(1, 2) + _f(2, tr.WINDOW_END.encode())
    step = _f(1, 4) + _f(2, b"train")
    op_event = (_f(1, 3) + _f(2, 150_000) + _f(3, 250_000)
                + _f(4, _f(1, 8) + _f(3, 47_000_000)))
    dev = (_f(2, b"/device:TPU:0") + stat_md + _f(4, _entry(3, op_md))
           + _f(3, _f(2, b"XLA Ops") + _f(3, 1000) + _f(4, op_event)))
    host = (_f(2, b"/host:CPU") + stat_md
            + _f(4, _entry(1, begin)) + _f(4, _entry(2, end))
            + _f(4, _entry(4, step))
            + _f(3, _f(2, b"python3") + _f(3, 900)
                 + _f(4, _f(1, 1) + _f(2, 100_000) + _f(3, 5_000))
                 + _f(4, _f(1, 4) + _f(2, 200_000) + _f(3, 300_000)
                      + _f(4, _f(1, 9) + _f(4, -3)))
                 + _f(4, _f(1, 2) + _f(2, 1_100_000) + _f(3, 5_000))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_f(1, host) + _f(1, dev))
    host_p, dev_p = sr.read_xplane(str(path))
    (op,) = dev_p.lines[0].events
    assert (op.name, op.start_ns, op.duration_ns) == (
        "%fusion.2 = bf16[4] fusion(...)", 1150.0, 250.0)
    assert op.stats == [("tf_op", FWD + "pair_dist/gather:"),
                        ("device_offset_ps", 47_000_000)]
    assert host_p.lines[0].events[1].stats == [("step_num", -3)]
    sc = sr.scope_planes([host_p, dev_p])
    assert (sc.t0, sc.t1) == (1000.0, 2000.0)
    assert sc.devices[0].ops == [(1150.0, 1400.0, op.name,
                                  FWD + "pair_dist/gather")]
    assert sr.self_ns(sc, "train") == (300.0, 1)


def test_read_xplane_agrees_with_profile_data_on_a_real_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.StepTraceAnnotation("train", step_num=3):
        with jax.profiler.TraceAnnotation("dispatch", step=3, chunk=1):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.newest_xplane(str(tmp_path))
    mine, theirs = sr.read_xplane(path), ProfileData.from_file(path).planes
    count = 0
    for a, b in zip(mine, theirs, strict=True):
        assert a.name == b.name
        for la, lb in zip(a.lines, b.lines, strict=True):
            assert la.name == lb.name
            for ea, eb in zip(la.events, lb.events, strict=True):
                assert ea.name == eb.name
                assert ea.start_ns == pytest.approx(eb.start_ns, abs=1)
                assert ea.duration_ns == pytest.approx(eb.duration_ns, abs=1)
                assert dict(ea.stats) == dict(eb.stats)
                count += 1
    assert count > 4
    steps = [e for p in mine for ln in p.lines for e in ln.events
             if e.name == "train"]
    assert dict(steps[0].stats)["step_num"] == 3


def test_new_metric_files_name_readers_and_cells_that_exist():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    new = [m for m in manifest["per_layer"]
           if m["name"].endswith("_ms") and m["name"] not in (
               "loop_gap_ms", "step_device_ms", "pallas_kernel_ms")]
    assert len(new) == 13
    for m in new:
        assert m["unit"] == "ms/step" and m["better"] == "lower"
        assert set(m["workloads"]) <= set(cells)
        with open(os.path.join(REPO, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] in ("op_time", "scope_time", "host_self_time")


NEW_METRICS = ["cluster_aggregate_ms", "csr_segment_sum_ms",
               "csr_segment_reduce_1d_ms", "cluster_att_fwd_ms",
               "cluster_att_bwd_ms", "csr_att_bwd_edges_ms", "encoder_ms",
               "agg_glue_ms", "pair_dist_ms", "pair_dist_bwd_ms",
               "optimizer_ms", "step_unattributed_ms", "loop_host_ms"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_the_harness_reads_each_new_metric_from_its_files_alone(
        metric, planes, monkeypatch):
    """The drop-in promise for this PR's files: ``run.py``'s own loader
    finds the metric's data file and reader by name and gets a number
    from a trace that holds what the metric reads."""
    ops = planes[1].lines[1].events
    for i, k in enumerate(["cluster_aggregate", "csr_segment_reduce_1d",
                           "cluster_att_fwd", "cluster_att_bwd",
                           "csr_att_bwd_edges"]):
        ops.append(ev(f"%{k}.{i}" + CC, 1800 + 10 * i, 10,
                      BWD + f"encoder/conv1/aggregate/{k}/pallas_call"))
    red, sc = tr.reduce_planes(planes), sr.scope_planes(planes)
    monkeypatch.setattr(sr, "load", lambda root, r: sc if r is red else None)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"] if m["name"] == metric]
    out = bench_run.read_layer_metrics(REPO, entry, red, {"steps_traced": 2},
                                       None)
    assert out[metric]["unit"] == "ms/step" and out[metric]["value"] > 0
