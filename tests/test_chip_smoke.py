"""chip_smoke.py: the parent stays off jax, refuses anything but a TPU,
its checkers tell a good run from each way a run can look good without
being one, and every phase's own command line runs end to end (here: on
the CPU at a tiny size, where the commands exit 0 and only the verdict
"this was a TPU with Pallas kernels" fails)."""

import ast
import copy
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402 — jax-free by contract (tested below)


# --- (a) no chip, no pass -----------------------------------------------------


def test_cpu_run_fails_within_seconds():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT,
                                                        "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          cwd=ROOT, timeout=120)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "not 'tpu'" in proc.stderr


# --- (b) the parent never imports jax or the package --------------------------


def test_parent_imports_neither_jax_nor_the_package():
    with open(os.path.join(ROOT, "chip_smoke.py")) as f:
        tree = ast.parse(f.read())
    roots = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            roots |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            roots.add((node.module or "").split(".")[0])
    assert not roots & {"jax", "jaxlib", "hyperspace_tpu", "flax", "optax"}
    # and importing it pulled neither in (this process has jax loaded
    # from conftest, so ask a fresh interpreter)
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke; "
            "print(sorted(m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'hyperspace_tpu')))" % ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, check=True).stdout
    assert out.strip() == "[]"


# --- (c) the checkers, fed recorded child outputs -----------------------------


GOOD_MANIFEST = {"event": "run_manifest", "backend": "tpu",
                 "device_kind": "TPU v5 lite", "device_count": 1,
                 "kernel_mode": "pallas",
                 "data": {"dataset": "ogbn-arxiv", "source": "disk",
                          "num_nodes": 169343, "num_edges": 1166243}}
GOOD_RECORDS = [
    GOOD_MANIFEST,
    {"step": 50, "ts": 1.0, "host": "", "loss": 1.31},
    {"step": 100, "ts": 2.0, "host": "", "loss": 1.05},
    {"step": 150, "ts": 3.0, "host": "", "loss": 0.93},
    {"event": "telemetry_summary", "steps": 150, "ctr/jax/compile_s": 41.5,
     "ctr/jax/compile_cache_hit": 0, "ctr/jax/compile_cache_miss": 37},
]
GOOD_RESULT = {"workload": "hgcn", "task": "lp", "dataset": "ogbn-arxiv",
               "source": "disk", "num_nodes": 169343, "num_edges": 1166243,
               "steps": 150, "loss": 0.93, "roc_auc": 0.71}
TRAINER = dict(chips=1, nodes=169343, edges=1166243, need_auc=True)


def _trainer_problems(result=None, records=None, **kw):
    return cs.check_trainer(GOOD_RESULT if result is None else result,
                            GOOD_RECORDS if records is None else records,
                            **{**TRAINER, **kw})


def _records(**manifest_changes):
    recs = copy.deepcopy(GOOD_RECORDS)
    recs[0].update(manifest_changes)
    return recs


def test_good_trainer_record_is_accepted():
    assert _trainer_problems() == []
    assert cs.device_of(cs.manifest_of(GOOD_RECORDS)) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert cs.losses_of(GOOD_RECORDS) == [(50, 1.31), (100, 1.05),
                                          (150, 0.93)]
    assert cs.step_ms(GOOD_RECORDS) == 20.0  # 2 s over 100 steps
    assert cs.step_ms(GOOD_RECORDS[:2]) is None


def _nan_loss():
    recs = copy.deepcopy(GOOD_RECORDS)
    recs[2]["loss"] = float("nan")
    return dict(records=recs)


def _rising_loss():
    recs = copy.deepcopy(GOOD_RECORDS)
    recs[3]["loss"] = 1.4
    return dict(records=recs)


@pytest.mark.parametrize("case, needle", [
    (_nan_loss, "non-finite loss"),
    (lambda: dict(result={**GOOD_RESULT, "loss": None}), "non-finite loss"),
    (_rising_loss, "did not fall"),
    (lambda: dict(result={**GOOD_RESULT, "source": "synthetic"}),
     "'synthetic'"),
    (lambda: dict(result={**GOOD_RESULT, "num_nodes": 16384}), "16384"),
    (lambda: dict(records=_records(backend="cpu")), "platform is 'cpu'"),
    (lambda: dict(records=_records(kernel_mode="xla")),
     "kernel mode is 'xla'"),
    (lambda: dict(records=_records(kernel_mode="interpret")),
     "kernel mode is 'interpret'"),
    (lambda: dict(records=_records(device_count=4)), "4 device(s)"),
    (lambda: dict(result={**GOOD_RESULT, "roc_auc": 0.5}), "ROC-AUC"),
    (lambda: dict(result={**GOOD_RESULT, "roc_auc": None}), "ROC-AUC"),
    (lambda: dict(records=_records(data={"source": "synthetic",
                                         "num_nodes": 16384})),
     "manifest's data record disagrees"),
], ids=["nan-logged-loss", "nan-final-loss", "rising-loss", "synthetic",
        "toy-graph", "cpu", "twin", "interpreter", "wrong-chip-count",
        "auc-at-chance", "auc-missing", "manifest-data"])
def test_bad_trainer_records_are_refused(case, needle):
    problems = _trainer_problems(**case())
    assert problems and any(needle in p for p in problems), problems


def test_log_without_manifest_is_refused():
    with pytest.raises(cs.PhaseFailed, match="run_manifest"):
        cs.check_trainer(GOOD_RESULT, GOOD_RECORDS[1:], **TRAINER)


@pytest.fixture(scope="module")
def ball():
    rng = np.random.default_rng(0)
    v = rng.standard_normal((400, 10)) * 0.3
    table = (np.tanh(np.linalg.norm(v, axis=1, keepdims=True))
             * v / np.linalg.norm(v, axis=1, keepdims=True) * 0.9)
    return table.astype(np.float32)


def _exact_answer(table, ids, k):
    d = cs.poincare_dist64(table[ids], table, 1.0)
    d[np.arange(len(ids)), ids] = np.inf
    order = np.argsort(d, axis=1)[:, :k]
    return order, np.take_along_axis(d, order, axis=1)


def test_topk_reference_accepts_right_and_refuses_wrong(ball):
    ids = [3, 17, 250]
    nbrs, dists = _exact_answer(ball, ids, 5)
    assert cs.check_topk(ball, 1.0, ids, 5, nbrs.tolist(),
                         dists.astype(np.float32).tolist()) == []
    # a wrong neighbour id: the 40th nearest served at rank 2
    d = cs.poincare_dist64(ball[ids], ball, 1.0)
    wrong = nbrs.copy()
    wrong[1, 2] = np.argsort(d[1])[40]
    problems = cs.check_topk(ball, 1.0, ids, 5, wrong.tolist(),
                             dists.tolist())
    assert any("query id 17 rank 2" in p for p in problems), problems
    # a right id with a wrong distance
    off = dists.copy()
    off[0, 0] += 1e-3
    assert any("served distance" in p for p in cs.check_topk(
        ball, 1.0, ids, 5, nbrs.tolist(), off.tolist()))
    # the query itself among its neighbours, NaN, and a short answer
    selfish = nbrs.copy()
    selfish[2, 0] = 250
    assert cs.check_topk(ball, 1.0, ids, 5, selfish.tolist(), dists.tolist())
    nan = dists.copy()
    nan[0, 1] = np.nan
    assert cs.check_topk(ball, 1.0, ids, 5, nbrs.tolist(), nan.tolist())
    assert cs.check_topk(ball, 1.0, ids, 5, nbrs[:, :4].tolist(),
                         dists[:, :4].tolist())


def test_topk_ties_within_tolerance_are_the_same_answer(ball):
    """Two rows at (numerically) the same distance may come back in
    either order."""
    table = ball.copy()
    table[11] = table[10]  # an exact tie for every query
    ids = [0, 5]
    nbrs, dists = _exact_answer(table, ids, 399)
    swapped = nbrs.copy()
    for q in range(len(ids)):
        i, j = (np.flatnonzero(nbrs[q] == t)[0] for t in (10, 11))
        swapped[q, i], swapped[q, j] = nbrs[q, j], nbrs[q, i]
    assert cs.check_topk(table, 1.0, ids, 399, swapped.tolist(),
                         dists.tolist()) == []


GOOD_HEALTH = {"ok": True, "backend": "tpu", "kernel_mode": "pallas"}
GOOD_STATS = {"backend": "tpu", "kernel_mode": "pallas", "prewarmed": 8,
              "recompiles": 21}


@pytest.mark.parametrize("health, first, last, needle", [
    (GOOD_HEALTH, GOOD_STATS, GOOD_STATS, None),
    ({**GOOD_HEALTH, "backend": "cpu"}, GOOD_STATS, GOOD_STATS,
     "platform is 'cpu'"),
    (GOOD_HEALTH, GOOD_STATS, {**GOOD_STATS, "kernel_mode": "xla"},
     "kernel mode is 'xla'"),
    (GOOD_HEALTH, GOOD_STATS, {**GOOD_STATS, "recompiles": 22},
     "recompiles moved"),
    (GOOD_HEALTH, {**GOOD_STATS, "prewarmed": 0}, GOOD_STATS,
     "warmed nothing"),
    ({**GOOD_HEALTH, "ok": False}, GOOD_STATS, GOOD_STATS, "healthz not ok"),
], ids=["good", "cpu", "twin", "recompile", "cold", "draining"])
def test_server_payload_checks(health, first, last, needle):
    problems = cs.check_server(health, first, last)
    if needle is None:
        assert problems == []
    else:
        assert any(needle in p for p in problems), problems


def test_probe_verdict():
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert cs.check_probe(tpu, 1) == []
    assert cs.check_probe({**tpu, "platform": "cpu"}, 1)
    assert cs.check_probe(tpu, 4) and cs.check_probe({**tpu, "count": 4}, 1)
    assert cs.check_probe({**tpu, "count": 4}, 4) == []


def test_mesh_comparisons():
    curve = [(10, 1.30), (20, 1.10), (30, 1.00)]
    near = [(s, v * (1 + 5e-5)) for s, v in curve]
    assert cs.check_loss_curves(near, curve) == []
    assert cs.check_loss_curves([(s, v * 1.01) for s, v in curve], curve)
    assert cs.check_loss_curves(curve[:2], curve)
    assert cs.check_loss_curves([(10, float("nan"))] + curve[1:], curve)

    def mem(*in_use):
        return [{"id": i, "bytes_in_use": b, "peak_bytes_in_use": 2e9}
                for i, b in enumerate(in_use)]

    one = mem(140e6)
    assert cs.check_memory(mem(70e6, 72e6, 70e6, 71e6), one) == []
    # the whole graph parked on device 0 beside its shard
    assert cs.check_memory(mem(135e6, 40e6, 40e6, 40e6), one)
    # a device of four holding as much as the one-chip run's
    assert cs.check_memory(mem(141e6, 139e6, 139e6, 139e6), one)
    # no statistics (a backend that keeps none) is not a pass
    assert cs.check_memory(mem(None, None, None, None), one)
    records = [{"event": "telemetry_summary", "device_memory": one}]
    assert cs.memory_of(records) == one


# --- (d) every phase's command line, end to end at a tiny size ----------------


TINY = dataclasses.replace(
    cs.FULL,
    graph=dict(num_nodes=600, num_edges=4000, num_classes=5, feat_dim=128,
               sub_size=60),
    nodes=600, edges=4000, mean_steps=100, att_steps=10, att_log_every=5,
    tree=(3, 3), tree_nodes=40, embed_steps=100,
    embed_overrides=("batch_size=64",), query_sizes=(1, 5), k=3,
    mesh_steps=10, mesh_log_every=5)


def _only_the_chip_is_missing(problems, *also):
    """On the CPU every child still says where it ran — so the only
    problems are the platform and the kernel mode that follows (and
    what ``also`` names)."""
    assert problems, "a CPU run must not pass"
    allowed = ("platform is 'cpu'", "kernel mode is 'xla'", *also)
    rest = [p for p in problems if not any(a in p for a in allowed)]
    assert rest == [], rest


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    base = tmp_path_factory.mktemp("chip_smoke")
    work, out = str(base / "work"), str(base / "out")
    os.makedirs(work)
    os.makedirs(out)
    rn = cs.Runner(work, out, budget_s=900.0)
    yield rn, cs.graph_dataset(rn, TINY)
    rn.stop_all()


@pytest.fixture(autouse=True)
def _children_on_the_cpu(monkeypatch):
    # the children inherit this process's environment: on the CPU, and
    # not over conftest's eight virtual devices
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)


def test_probe_child_reports_the_device(tiny_run):
    rn, _ = tiny_run
    dev = cs.phase_probe(rn, name="probe_test")
    assert dev == {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.mark.parametrize("att", [False, True], ids=["mean", "attention"])
def test_trainer_phase_commands_run(tiny_run, att, capsys):
    rn, data_root = tiny_run
    manifest, problems = cs.phase_trainer(rn, TINY, data_root, 1, att=att)
    _only_the_chip_is_missing(problems)
    assert manifest["data"]["source"] == "disk"
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == ("trainer_att" if att else "trainer")
    assert line["num_nodes"] == 600 and line["source"] == "disk"
    assert line["compile_s"] > 0 and len(line["losses"]) >= 2


def test_server_phase_commands_run(tiny_run, capsys):
    """train → export → serve-http (both scan modes) → drain, with every
    served answer matching the float64 reference on the CPU too."""
    rn, _ = tiny_run
    manifest, problems = cs.phase_server(rn, TINY, 1)
    _only_the_chip_is_missing(problems)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "server" and line["num_nodes"] == 40
    assert line["answers_match_reference"] is True
    assert line["scan_modes"] == ["two_stage", "fused"]


def test_mesh_phase_commands_run(tiny_run, monkeypatch, capsys):
    """The --chips 4 path on four virtual CPU devices: the trainer picks
    the mesh by itself and the same command runs again on one device.
    (The CPU keeps no allocator statistics and is no TPU, so the verdict
    fails on exactly those.)"""
    rn, data_root = tiny_run
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    # what ONE_CHIP_ENV does on a TPU host, for the CPU
    monkeypatch.setattr(cs, "ONE_CHIP_ENV", {"XLA_FLAGS": ""})
    monkeypatch.setattr(cs, "check_probe", lambda dev, chips: (
        [] if dev["count"] == chips else [f"{dev['count']} devices"]))
    manifest, problems = cs.phase_mesh(rn, TINY, data_root)
    assert manifest["device_count"] == 4
    _only_the_chip_is_missing(problems, "memory statistics missing")
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "mesh" and line["devices"] == 4
    assert [s for s, _ in line["losses_four"]] == [5, 10]
