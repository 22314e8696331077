"""The two per-layer metrics that say the attention arm's per-edge
scalar moves took their new forms (PERF.md §3): each is read by the
harness from its files alone (``BENCHMARK.json``'s entry,
``benchmark/layer_metrics/<name>.json``, an existing reader), is silent
on a program from before the change, and matches what the lowered step
really emits: a ``sort`` under scope ``aggregate`` in the backward of
both layers, a Pallas call named ``csr_segment_expand_1d``."""

import fnmatch
import json
import os
import re

import pytest

from benchmark import run as bench_run
from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr
from tests.benchmark.helpers import REPO
from tests.benchmark.test_scope_reduce import BWD, FWD, ev, line, plane
from tests.tiny_lp import lp_step

CC = ' = f32[8,4,128] custom-call(...), custom_call_target="tpu_custom_call"'
METRICS = ["agg_involution_sort_time", "csr_segment_expand_1d_time"]


def planes(ops):
    return [plane("/host:CPU", [line("python3", [
                ev(tr.WINDOW_BEGIN, 1000, 5), ev(tr.WINDOW_END, 2000, 5)])]),
            plane("/device:TPU:0", [
                line("XLA Modules", [ev("jit_step", 1100, 700)]),
                line("XLA Ops", ops)])]


# a parent's step: the picks are gathers, the decoder sorts under pair_dist
PARENT = [
    ev("%fusion.34 = f32[1571840]", 1100, 100,
       FWD + "encoder/conv0/aggregate/gather"),
    ev("%fusion.35 = bf16[1571840]", 1200, 100,
       BWD + "encoder/conv0/aggregate/gather"),
    ev("%csr_segment_reduce_1d.3" + CC, 1300, 20,
       BWD + "encoder/conv0/aggregate/csr_segment_reduce_1d/pallas_call"),
    ev("%sort.1 = (s32[3762176], bf16[3762176])", 1320, 50,
       BWD + "pair_dist/sort"),
]
CHANGE = PARENT[2:] + [
    ev("%csr_segment_expand_1d.2" + CC, 1400, 30,
       FWD + "encoder/conv0/aggregate/csr_segment_expand_1d/pallas_call"),
    ev("%sort.2 = (s32[1571840], f32[1571840], f32[1571840])", 1500, 60,
       BWD + "encoder/conv1/aggregate/sort"),
    ev("%sort.3 = (s32[1571840], f32[1571840], f32[1571840])", 1600, 60,
       BWD + "encoder/conv0/aggregate/sort"),
]
WANT_NS = {"agg_involution_sort_time": 120, "csr_segment_expand_1d_time": 30}


def _read(metric, ops, monkeypatch):
    pl = planes(ops)
    red, sc = tr.reduce_planes(pl), sr.scope_planes(pl)
    monkeypatch.setattr(sr, "load", lambda root, r: sc if r is red else None)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"] if m["name"] == metric]
    assert len(entry) == 1
    assert entry[0]["workloads"] == ["hgcn_att_arxiv_lp.fullgraph"]
    return bench_run.read_layer_metrics(REPO, entry, red,
                                        {"steps_traced": 2}, None)


@pytest.mark.parametrize("metric", METRICS)
def test_the_harness_reads_the_metric_from_its_files_alone(metric,
                                                           monkeypatch):
    out = _read(metric, CHANGE, monkeypatch)
    assert out[metric]["unit"] == "ms/step"
    assert out[metric]["value"] == pytest.approx(WANT_NS[metric] / 2 * 1e-6)


@pytest.mark.parametrize("metric", METRICS)
def test_the_metric_is_silent_on_a_program_from_before_the_change(
        metric, monkeypatch):
    assert _read(metric, PARENT, monkeypatch) == {}


@pytest.fixture(scope="module")
def op_names():
    """{arm: every op_name of the lowered tiny step, operation kept}."""
    mp = pytest.MonkeyPatch()
    mp.setenv("HYPERSPACE_KERNELS", "interpret")
    out = {}
    try:
        for att in (False, True):
            step, state = lp_step(att)
            text = step.lower(state).as_text(debug_info=True)
            out[att] = set(re.findall(r'loc\("(jit\(train_step_lp\)/[^"]*)"',
                                      text))
    finally:
        mp.undo()
    return out


def _spec(metric):
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


def test_the_sort_pattern_matches_what_the_step_emits(op_names):
    pats = _spec("agg_involution_sort_time")["patterns"]
    hit = {att: sorted(p for p in names
                       if any(fnmatch.fnmatchcase(p, pat) for pat in pats))
           for att, names in op_names.items()}
    assert hit[False] == []      # the mean arm never permutes by the involution
    # one sort a layer, in the backward, and the decoder's sort is not read
    assert len(hit[True]) == 2, hit[True]
    for layer in ("conv0", "conv1"):
        assert any(p.endswith(f"encoder/{layer}/aggregate/sort")
                   and "transpose(" in p for p in hit[True]), hit[True]
    assert any(p.endswith("pair_dist/sort") for p in op_names[True])


def test_the_kernel_pattern_matches_the_call_name(op_names):
    (pat,) = _spec("csr_segment_expand_1d_time")["patterns"]
    # the profile names the operation after the instruction, and the
    # instruction after pl.pallas_call(name=...)
    assert fnmatch.fnmatchcase("%csr_segment_expand_1d.2" + CC, pat)
    assert not fnmatch.fnmatchcase("%csr_segment_reduce_1d.3" + CC, pat)
    calls = [p for p in op_names[True] if "/csr_segment_expand_1d/" in p]
    assert calls and all("/aggregate/" in p and "transpose(" not in p
                         for p in calls), calls
    assert not [p for p in op_names[False] if "csr_segment_expand_1d" in p]
