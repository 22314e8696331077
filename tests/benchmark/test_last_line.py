"""The rest of a run at a tiny size on the CPU, with the look for a chip
lifted inside the test: the last line of standard output carries
exactly the contract's keys, with ``--trace 0`` the cell's end-to-end
metrics and with ``--trace 1`` its per-layer ones."""

import json

import pytest

from benchmark import run as bench_run
from tests.benchmark import helpers

DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes",
               # the device counters the peak was made from
               "allocator_peak_bytes", "live_bytes", "reserved_bytes"}
CHECKS = {"loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
          "grad_norm_gap", "change_norm_gap", "grad_difference",
          "twin_loss_gap", "twin_grad_norm_gap", "twin_grad_difference",
          "compiles_in_window"}


def _last_line(capsys):
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("cell", ["hgcn_arxiv_lp.fullgraph",
                                  "hgcn_att_arxiv_lp.fullgraph"])
def test_trace_0_then_trace_1(cell, tmp_path, monkeypatch, capsys,
                              restore_compile_cache):
    root = helpers.tiny_root(tmp_path)
    helpers.lift_refusals(monkeypatch)
    argv = ["--workload", cell, "--seed", str(2**31 + 17), "--seconds",
            "0.5"]

    assert bench_run.main(argv + ["--trace", "0"], root=root) == 0
    line, err = _last_line(capsys)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"samples_per_s_chip", "setup_s"}
    assert line["metrics"]["samples_per_s_chip"]["unit"] == "samples/s/chip"
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == DEVICE_KEYS
    assert set(line["checks"]) == CHECKS
    assert line["checks"]["compiles_in_window"] == {"value": 0.0,
                                                    "limit": 0.0}
    # each number compared stands beside its limit at the end of stderr
    tail = err.strip().splitlines()[-len(CHECKS):]
    assert all(t.startswith("check ") and "(limit " in t for t in tail)

    assert bench_run.main(argv + ["--trace", "1"], root=root) == 0
    line, _ = _last_line(capsys)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "breakdown", "device", "checks"]
    assert line["correct"] is True
    assert set(line["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    # only per-layer metrics; a reader with nothing to read stays silent
    # (no kernel and no program line on the CPU) and never reports 0
    assert {"device_idle_pct", "step_device_ms", "step_mfu_pct"} <= set(
        line["metrics"])
    assert not {"samples_per_s_chip", "setup_s"} & set(line["metrics"])
    assert all(m["value"] != 0 for m in line["metrics"].values())
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10
