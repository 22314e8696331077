"""A later PR adds a configuration, a traffic mix, a driver, a reader
and a per-layer metric as NEW files and NEW manifest entries only: a
dummy of each, dropped into a temporary copy, is found and run with no
edit to any file that was there."""

import hashlib
import json
import os

from benchmark import run as bench_run
from tests.benchmark import helpers

DRIVER = '''
def run(ctx):
    import types
    red = types.SimpleNamespace(devices=[types.SimpleNamespace(
        name="d0", ops=[(0.0, 5e8, "dummy_op")], modules=[])],
        host_spans=[], t0=0.0, t1=1e9, window_ns=1e9)
    return {"attempted": ctx.traffic["requests"], "failed": 0,
            "end_to_end": {"dummy_rate": ctx.config["rate"] * ctx.seconds,
                           "setup_s": 0.5},
            "memory_peak_bytes": 1, "facts": {"scale": ctx.seed},
            "trace": red if ctx.trace else None,
            "checks": [("dummy_gap", 0.0, ctx.limits["dummy_gap"])]}
'''
READER = '''
def read(red, facts, peaks, spec):
    return spec["factor"] * facts["scale"]
'''


def _tree_digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_dummy_cell_runs_from_new_files_alone(tmp_path, monkeypatch, capsys):
    root = helpers.tiny_root(tmp_path)
    helpers.lift_refusals(monkeypatch)
    before = _tree_digest(os.path.join(root, "benchmark"))

    b = os.path.join(root, "benchmark")
    new = {
        "configs/dummy_cfg.json": json.dumps({"rate": 3.0}),
        "traffic/dummy_mix.json": json.dumps(
            {"driver": "dummy_driver", "chips": 1, "requests": 7}),
        "drivers/dummy_driver.py": DRIVER,
        "readers/dummy_reader.py": READER,
        "layer_metrics/dummy_metric.json": json.dumps(
            {"reader": "dummy_reader", "factor": 2.0}),
        "limits/dummy_cfg.dummy.json": json.dumps(
            {"limits": {"dummy_gap": 0.0}}),
    }
    for rel, text in new.items():
        with open(os.path.join(b, rel), "w") as f:
            f.write(text)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "dummy_cfg", "source": "none", "reduced": [],
        "file": "benchmark/configs/dummy_cfg.json", "why": "drop-in"})
    manifest["workloads"].append({
        "name": "dummy_cfg.dummy", "config": "dummy_cfg",
        "traffic": "dummy_mix", "chips": 1, "why": "drop-in"})
    manifest["end_to_end"].append({
        "name": "dummy_rate", "unit": "x/s", "better": "higher",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["dummy_cfg.dummy"]})
    manifest["per_layer"].append({
        "name": "dummy_metric", "unit": "x", "better": "higher",
        "source": "program_counter", "layer": "dummy",
        "moves": "dummy_rate", "workloads": ["dummy_cfg.dummy"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    argv = ["--workload", "dummy_cfg.dummy", "--seed", "5", "--seconds", "2"]
    assert bench_run.main(argv + ["--trace", "0"], root=root) == 0
    line0 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line0["correct"] is True and line0["attempted"] == 7
    assert line0["metrics"] == {
        "dummy_rate": {"value": 6.0, "unit": "x/s"},
        "setup_s": {"value": 0.5, "unit": "s"}}

    assert bench_run.main(argv + ["--trace", "1"], root=root) == 0
    line1 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # only the metrics that list the cell (or list none) are asked of it
    assert line1["metrics"]["dummy_metric"] == {"value": 10.0, "unit": "x"}
    assert "att_kernel_ms" not in line1["metrics"]
    assert line1["device"]["busy_s"] == 0.5

    after = _tree_digest(os.path.join(root, "benchmark"))
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == sorted(new)
