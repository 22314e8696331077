"""Shared by the benchmark's CPU tests: a temporary copy of the
benchmark (``BENCHMARK.json`` + ``benchmark/``) whose configurations are
cut to a tiny generated graph, and the two refusals lifted."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_GRAPH = {"num_nodes": 1500, "num_edges": 9000, "num_classes": 6,
              "feat_dim": 16, "sub_size": 60}
TINY_MODEL = {"feat_dim": 16, "hidden_dims": [16, 8]}


def tiny_config(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg["dataset"]["generator_args"] = dict(TINY_GRAPH)
    cfg["dataset"].update(num_nodes=TINY_GRAPH["num_nodes"],
                          num_edges=TINY_GRAPH["num_edges"],
                          feat_dim=TINY_GRAPH["feat_dim"])
    cfg["model"].update(TINY_MODEL)
    cfg["cli_overrides"] = [
        "hidden_dims=[16, 8]" if o.startswith("hidden_dims=") else o
        for o in cfg["cli_overrides"]]
    return cfg


def tiny_root(tmp_path, limits: float = 0.5) -> str:
    """Copy the benchmark into ``tmp_path`` at tiny sizes.  The limits on
    the stated bf16 lanes' numbers are set to ``limits`` (a few thousand
    pairs round far coarser than two million); the twin's limits, at
    float32 lanes, stay as committed."""
    root = str(tmp_path / "root")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    def rewrite(sub, change):
        d = os.path.join(root, "benchmark", sub)
        for name in os.listdir(d):
            path = os.path.join(d, name)
            with open(path) as f:
                doc = change(json.load(f))
            with open(path, "w") as f:
                json.dump(doc, f)

    rewrite("configs", tiny_config)
    rewrite("limits", lambda doc: {
        "limits": {k: v if k.startswith("twin_") else limits
                   for k, v in doc["limits"].items()}})
    rewrite("traffic", lambda doc: {**doc, "trace_seconds": 0.2})
    return root


def one_device(monkeypatch):
    """The suite gives the CPU eight virtual devices and the trainer
    builds a mesh from all it sees: show it the cells' one."""
    import jax

    real = jax.devices
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: real(*a, **k)[:1])


def lift_refusals(monkeypatch):
    """Let the harness run on the CPU: inside a test only."""
    from benchmark import guards, trace_reduce

    one_device(monkeypatch)
    monkeypatch.setattr(guards, "require_chip", lambda n: {
        "platform": "cpu-under-test", "kind": "TPU v5 lite", "count": n})
    monkeypatch.setattr(guards, "require_pallas", lambda: None)
    # on the CPU the operations run on a host thread's line
    monkeypatch.setattr(trace_reduce, "DEVICE_PLANE_PREFIX", "/host:CPU")
    monkeypatch.setattr(trace_reduce, "OP_LINE", "tf_XLAPjRtCpuClient")
    monkeypatch.setattr(trace_reduce, "MODULE_LINE", "no-such-line")
