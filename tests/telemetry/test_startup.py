"""The start-up timeline (telemetry/trace.py): nesting and self times,
the closed path, the bound, who closes it (``run_loop``, ``cli_session``,
the serve CLI, the host-table trainer), what may not touch the closed
report, the Chrome dump beside an enabled tracer, jax's trace / lower /
compile events as spans, and the timeline of whole processes:
``cli.train`` and the benchmark's traced run.

``tests/conftest.py`` closes the suite's own timeline; the in-process
tests here open fresh ones (fixture ``timeline``), the whole-process
ones run children."""

import importlib.util
import json
import os
import subprocess
import sys
import threading

import pytest

from hyperspace_tpu.telemetry import registry, trace
from tests.benchmark import helpers

REPO = helpers.REPO


@pytest.fixture
def timeline(monkeypatch):
    """A fresh open timeline in place of the process's closed one, the
    default tracer off; the closed one comes back afterwards."""
    tl = trace._Startup()
    monkeypatch.setattr(trace, "_startup", tl)
    monkeypatch.setattr(trace, "_startup_open", tl)
    t = trace.default_tracer()
    monkeypatch.setattr(t, "enabled", False)
    monkeypatch.setattr(t, "keep_events", False)
    return tl


def _parts(rep):
    return (sum(e["self_s"] for e in rep["spans"].values())
            + rep["unattributed_s"])


# --- the record and the report ------------------------------------------------


def test_nesting_and_self_times_sum_to_the_total(timeline):
    tl = timeline
    tl.records[0][1] = 0.0                      # the process's start
    tl.records[1][1:3] = [0.0, 0.5]             # pre_program
    a = tl.begin("a", 1.0, None)
    a1 = tl.begin("a1", 2.0, {"k": 1})

    def other_thread():
        b = tl.begin("b", 2.5, None)            # no span open on it: root
        tl.add("late", 3.0, 3.5, None)          # after the fact, inside b
        tl.end(b, 4.0)

    th = threading.Thread(target=other_thread)
    th.start()
    th.join()
    tl.end(a1, 3.0)                             # a child outlives nothing
    tl.add("done", 4.2, 4.4, None)              # after the fact, inside a
    tl.end(a, 5.0)
    still = tl.begin("still_open", 9.0, None)   # the close ends it
    assert tl.close(10.0) and not tl.close(11.0)

    by = {r[0]: i for i, r in enumerate(tl.records)}
    parent = lambda name: tl.records[by[name]][5]
    assert parent("a") == 0 and parent("a1") == by["a"]
    assert parent("b") == 0 and parent("late") == by["b"]
    assert parent("done") == by["a"] and parent("pre_program") == 0
    rep = trace.startup_report()
    assert rep["total_s"] == 10.0 and rep["dropped"] == 0
    own = {k: v["self_s"] for k, v in rep["spans"].items()}
    # one thread: duration less what the children cover; two threads:
    # the later start takes the overlap, so every instant counts once
    assert own == pytest.approx({
        "pre_program": 0.5, "a": 1.0 + 0.2 + 0.6, "a1": 0.5,
        "b": 0.5 + 0.5, "late": 0.5, "done": 0.2, "still_open": 1.0})
    assert rep["spans"]["a"]["total_s"] == pytest.approx(4.0)
    assert rep["unattributed_s"] == pytest.approx(10.0 - 5.5)
    assert abs(_parts(rep) - rep["total_s"]) < 1e-6
    assert tl.records[still][2] == 10.0
    assert rep["spans"]["still_open"]["n"] == 1
    flat = trace.startup_fields()
    assert flat["startup/a_s"] == 4.0 and flat["startup/a_n"] == 1
    assert flat["startup/a_self_s"] == 1.8
    assert flat["startup/total_s"] == 10.0
    assert set(trace._STARTUP_COUNTERS) == {
        k.removeprefix("startup/ctr/") for k in flat
        if k.startswith("startup/ctr/")}
    spans = trace.startup_spans()
    assert [s["name"] for s in spans[:2]] == ["startup", "pre_program"]
    assert spans[by["a1"]]["args"] == {"k": 1}
    assert spans[by["a1"]]["parent"] == by["a"]


def test_root_starts_at_the_process_start():
    now = 1000.0
    origin, how = trace._process_start(now)
    assert how == "process_start" and origin < now
    # this interpreter has run for more than a moment, less than a day
    assert 0.01 < now - origin < 86400


def test_open_timeline_records_with_the_tracer_off(timeline):
    assert trace.tracing() and trace.startup_open()
    t = trace.default_tracer()
    before = t.total_fields()
    with trace.span("outer", {"x": 1}):
        with trace.span("inner"):
            pass
        trace.record_span("after_the_fact", 1.0, 2.0, {"y": 2})
    assert t.total_fields() == before        # the tracer is off: nothing
    names = [r[0] for r in timeline.records[2:]]
    assert names == ["outer", "inner", "after_the_fact"]
    assert [r[5] for r in timeline.records[2:]] == [0, 2, 2]
    assert "jax.profiler" in sys.modules     # and yet no annotation made
    assert trace._Span(None, "x")._ann is trace._NULL


def test_closed_timeline_is_the_disabled_path(timeline):
    trace.close_startup()
    assert not trace.startup_open() and not trace.tracing()
    assert trace.span("x") is trace.span("y", {"a": 1}) is trace._NULL
    n = len(timeline.records)
    with trace.span("x"):
        trace.record_span("y", 1.0, 2.0)
    assert len(timeline.records) == n
    trace.close_startup()                    # idempotent


def test_the_suites_own_timeline_is_closed():
    # tests/conftest.py closed it: every other test of the suite runs
    # the path a program runs after its first dispatch
    assert trace.startup_report() is not None
    assert trace.span("x") is trace._NULL or trace.default_tracer().enabled
    assert trace.importing("flax") is trace.span("x")


def test_bound_and_drop_count(timeline, monkeypatch):
    monkeypatch.setattr(trace, "_STARTUP_MAX", 6)
    for i in range(7):
        with trace.span(f"s{i}"):
            pass
    trace.record_span("late", 1.0, 2.0)
    assert len(timeline.records) == 6        # root, pre_program, s0..s3
    trace.close_startup()
    rep = trace.startup_report()
    assert rep["dropped"] == 4 and "s4" not in rep["spans"]
    assert abs(_parts(rep) - rep["total_s"]) < 1e-6


def test_run_loop_closes_where_its_first_dispatch_returns(timeline):
    """``run_loop`` reads the flag once a call and closes behind a local
    bool: its first dispatch's return ends the timeline, a foreign span
    called ``dispatch`` does not, later steps record nothing."""
    from hyperspace_tpu.cli.train import RunConfig
    from hyperspace_tpu.train.loop import run_loop

    with trace.span("dispatch"):             # not the loop's: no close
        pass
    assert trace.startup_open()
    seen = []

    def stepper(state):
        seen.append(trace.startup_open())
        return state + 1, 0.5

    state, _ = run_loop(RunConfig(steps=3), 0, stepper)
    assert state == 3 and seen == [True, False, False]
    rep = trace.startup_report()
    assert rep["spans"]["dispatch"]["n"] == 2    # the foreign one, the first
    assert rep["spans"]["train_step"]["n"] == 1  # open at the close: ended there
    assert abs(_parts(rep) - rep["total_s"]) < 1e-6


def test_reset_dump_and_enable_leave_the_closed_report(timeline, tmp_path):
    with trace.span("init", {"model": "m"}):
        pass
    trace.close_startup()
    first = json.dumps(trace.startup_report(), sort_keys=True)
    t = trace.enable(keep_events=True)
    t.reset()
    with trace.span("later"):
        pass
    path = str(tmp_path / "t.json")
    assert t.dump_chrome_trace(path) == 4    # the timeline's three lead
    with open(path) as f:
        evs = json.load(f)["traceEvents"]
    assert [e["name"] for e in evs] == ["startup", "pre_program", "init",
                                        "later"]
    assert evs[0]["args"] == {"origin": "process_start"}
    assert evs[2]["args"] == {"model": "m"}
    assert evs[0]["ts"] + evs[0]["dur"] <= evs[3]["ts"]
    assert t.dump_chrome_trace(path) == 0    # once: the first dump only
    t.reset()
    trace.enable(keep_events=False)
    trace.disable()
    assert json.dumps(trace.startup_report(), sort_keys=True) == first


def _dump(t, path):
    n = t.dump_chrome_trace(str(path))
    with open(path) as f:
        evs = json.load(f)["traceEvents"]
    assert n == len(evs)
    return evs


def test_tracer_keeps_its_events_while_the_timeline_records(timeline,
                                                            tmp_path):
    """The timeline is a second, passive sink: an enabled tracer holds
    every span as it always did, open timeline or not, and the dump
    draws a span both hold once."""
    t = trace.enable(keep_events=True)
    t.reset()
    with trace.span("train_step"):
        with trace.span("prep"):
            pass
        trace.record_span("compile", 1.0, 2.0, {"fun_name": "f"})
        with trace.span("dispatch"):
            pass
        trace.close_startup()                # train_step still open
    assert [e[0] for e in t._events] == ["prep", "compile", "dispatch",
                                         "train_step"]
    fields = t.total_fields()
    assert {fields[f"span/{n}_n"] for n in ("train_step", "prep",
                                            "compile", "dispatch")} == {1}
    evs = _dump(t, tmp_path / "t.json")
    assert sorted(e["name"] for e in evs) == [
        "compile", "dispatch", "pre_program", "prep", "startup",
        "train_step"]
    # the close cut the timeline's train_step short: the tracer's whole
    # one is the one drawn
    step = next(e for e in evs if e["name"] == "train_step")
    root = next(e for e in evs if e["name"] == "startup")
    assert step["ts"] + step["dur"] > root["ts"] + root["dur"]


def test_dump_with_the_timeline_still_open_loses_nothing(timeline,
                                                         tmp_path):
    """A dump before any close (a library caller's crash before its
    first dispatch): the tracer's events are all there, and so is what
    the timeline alone saw end, before the tracer was turned on."""
    with trace.span("load_graph"):
        pass
    t = trace.enable(keep_events=True)
    t.reset()
    with trace.span("query"):
        with trace.span("compile"):
            pass
    assert trace.startup_open()
    evs = _dump(t, tmp_path / "open.json")
    assert [e["name"] for e in evs] == ["pre_program", "load_graph",
                                        "compile", "query"]
    with trace.span("late"):
        pass
    trace.close_startup()
    evs = _dump(t, tmp_path / "closed.json")     # the first after the close
    assert [e["name"] for e in evs] == [
        "startup", "pre_program", "load_graph", "query", "compile", "late"]
    assert _dump(t, tmp_path / "again.json") == []


def test_cli_session_that_never_reaches_a_loop_dumps_its_set_up(
        timeline, tmp_path, capsys):
    """``cli_session``'s promise: a run that crashes in set-up, before
    any dispatch, still produces its trace, set-up spans included; and it
    leaves the timeline closed, whatever the workload got to."""
    from hyperspace_tpu.telemetry import cli_session

    with trace.span("import", {"module": "flax"}):   # before the session
        pass
    path = str(tmp_path / "crash.json")
    with pytest.raises(RuntimeError, match="init failed"):
        with cli_session(False, path):
            with trace.span("load_graph", {"dataset": "d"}):
                pass
            with trace.span("init"):
                raise RuntimeError("init failed")
    assert not trace.startup_open() and not trace.tracing()
    with open(path) as f:
        evs = json.load(f)["traceEvents"]
    assert [e["name"] for e in evs] == ["startup", "pre_program", "import",
                                        "load_graph", "init"]
    assert evs[3]["args"] == {"dataset": "d"}
    rep = trace.startup_report()
    assert rep["spans"]["init"]["n"] == 1
    assert abs(_parts(rep) - rep["total_s"]) < 1e-6
    assert "5 trace events" in capsys.readouterr().out


def test_serve_closes_the_timeline_before_its_first_request(
        timeline, tmp_path):
    """A serve-shaped process never enters ``run_loop``: the serve CLI
    closes the timeline once the server is built and prewarmed, so the
    first request already runs the closed path (``tracing()`` false: the
    batcher builds no span args), and the session's log carries the
    ``startup`` event."""
    import io

    import numpy as np

    from hyperspace_tpu.cli import serve
    from hyperspace_tpu.serve.artifact import export_artifact
    from hyperspace_tpu.train.logging import read_jsonl

    rng = np.random.default_rng(0)
    table = (rng.standard_normal((40, 4)) * 0.1).astype(np.float32)
    art = str(tmp_path / "art")
    export_artifact(art, table, ("poincare", 1.0))
    seen = []

    class Lines:
        """stdin: each line is read only once the server is ready."""

        def __iter__(self):
            seen.append((trace.startup_open(), trace.tracing()))
            yield json.dumps({"op": "topk", "ids": [1, 2], "k": 3}) + "\n"
            seen.append((trace.startup_open(), trace.tracing()))
            yield json.dumps({"op": "topk", "ids": [3], "k": 3}) + "\n"

    n_before = len(timeline.records)
    assert trace.startup_open()
    log = str(tmp_path / "serve.jsonl")
    cfg = serve.apply_overrides(serve.ServeConfig(), {
        "artifact": art, "prewarm": "3", "log": log})
    out = io.StringIO()
    stats = serve.run_serve(cfg, stdin=Lines(), stdout=out)
    assert seen == [(False, False), (False, False)]
    assert len(out.getvalue().splitlines()) == 2 and stats["served"] == 2
    rep = trace.startup_report()
    assert rep["spans"]["compile"]["n"] >= 1     # the build and the prewarm
    assert "query" not in rep["spans"]           # no request is start-up
    assert len(timeline.records) > n_before
    events = [r["event"] for r in read_jsonl(log) if "event" in r]
    assert events == ["run_manifest", "startup", "telemetry_summary"]
    # a second session of the process: no second start-up
    serve.run_serve(cfg, stdin=io.StringIO(""), stdout=io.StringIO())
    events = [r["event"] for r in read_jsonl(log) if "event" in r]
    assert events.count("startup") == 1


def test_host_table_trainer_closes_at_its_first_chunk(timeline):
    """The host-table trainer has no ``run_loop`` either: its first
    chunk's return ends the timeline."""
    import numpy as np

    from hyperspace_tpu.models import poincare_embed as pe
    from hyperspace_tpu.train import host_embed as he

    cfg = pe.PoincareEmbedConfig(num_nodes=24, dim=4, neg_samples=2,
                                 batch_size=8)
    state, opt = pe.init_state(cfg, 0)
    pairs = np.random.default_rng(0).integers(0, 24, (64, 2))
    trainer = he.HostPlannedTrainer.from_state(cfg, opt, state,
                                               chunk_steps=2)
    assert trace.startup_open()
    losses = trainer.run(pairs, 4)               # two chunks
    assert losses.shape == (4,) and not trace.startup_open()
    rep = trace.startup_report()
    assert rep["spans"]["host_chunk_dispatch"]["n"] == 1


# --- jax's events as spans ----------------------------------------------------


def test_jit_under_an_open_timeline_yields_its_three_spans(timeline):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def startup_probe_fn(x):
        return jnp.tanh(x) * 3.0

    base = {k: registry.default_registry().get(k)
            for k in ("jax/trace_s", "jax/lower_s", "jax/compile_s")}
    with trace.span("init", {"model": "probe"}):
        startup_probe_fn(jnp.ones((3,), jnp.float32)).block_until_ready()
    trace.close_startup()
    init = [r[0] for r in timeline.records].index("init")
    mine = [r for r in timeline.records
            if "startup_probe_fn" in str((r[4] or {}).get("fun_name"))]
    assert sorted(r[0] for r in mine) == ["compile", "jit_lower",
                                          "jit_trace"]
    assert all(r[5] == init for r in mine)   # caused by the open span
    comp = next(r for r in mine if r[0] == "compile")
    assert comp[4]["cache"] in ("hit", "miss", "off")
    rep = trace.startup_report()
    assert abs(_parts(rep) - rep["total_s"]) < 1e-6
    for k, was in base.items():
        assert registry.default_registry().get(k) > was
    assert rep["counters"]["jax/trace_s"] > 0


_CACHE_CHILD = """
import json, sys
from hyperspace_tpu import compile_cache
from hyperspace_tpu.telemetry import trace
import jax, jax.numpy as jnp
compile_cache.activate(sys.argv[1])

@jax.jit
def cache_probe_fn(x):
    return jnp.cos(x) + 2.0

cache_probe_fn(jnp.ones((5,), jnp.float32)).block_until_ready()
trace.close_startup()
rep = trace.startup_report()
mine = [s["args"]["cache"] for s in trace.startup_spans()
        if s["name"] == "compile" and "cache_probe_fn" in s["args"]["fun_name"]]
print(json.dumps({"cache": mine, "counters": rep["counters"],
                  "jax_imported": trace.startup_spans()[1]["args"]}))
"""


def _child(code, *argv, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    full.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env)
    out = subprocess.run([sys.executable, "-c", code, *argv], env=full,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out


def test_cache_reads_miss_then_hit_across_two_processes(tmp_path):
    cache = str(tmp_path / "cc")
    first = json.loads(_child(_CACHE_CHILD, cache).stdout.splitlines()[-1])
    second = json.loads(_child(_CACHE_CHILD, cache).stdout.splitlines()[-1])
    assert first["cache"] == ["miss"] and second["cache"] == ["hit"]
    assert first["counters"]["jax/compile_cache_miss"] >= 1
    assert second["counters"]["jax/compile_cache_miss"] == 0
    assert second["counters"]["jax/compile_cache_hit"] >= 1
    assert first["jax_imported"] == {"jax_imported": False}


# --- whole processes ----------------------------------------------------------

_CLI_CHILD = """
import json, sys
from hyperspace_tpu.cli import train
from hyperspace_tpu.data import graphs
from hyperspace_tpu.telemetry import trace

work = sys.argv[1]
root = graphs.ensure_arxiv_scale_dataset(
    work + "/data", seed=3, num_nodes=1500, num_edges=9000, num_classes=6,
    feat_dim=16, sub_size=60)
common = ["hgcn", "task=lp", "dataset=ogbn-arxiv", "data_root=" + root,
          "hidden_dims=[16, 8]", "steps=2", "graph_cache=true",
          "telemetry=true", "compile_cache_dir=" + work + "/cc"]
assert train.main(common + ["log=" + work + "/run1.jsonl"]) == 0
first = json.dumps(trace.startup_report(), sort_keys=True)
# a second build in the same process (the benchmark's twin has this
# shape): init, placement and compiles again, after the close
assert train.main(common + ["log=" + work + "/run2.jsonl"]) == 0
assert json.dumps(trace.startup_report(), sort_keys=True) == first
print(json.dumps([s for s in trace.startup_spans()
                  if s["name"] in ("prep", "read_csv", "load_graph")]))
"""


def test_cli_train_writes_one_startup_event(tmp_path):
    out = _child(_CLI_CHILD, str(tmp_path),
                 HYPERSPACE_CACHE_DIR=str(tmp_path / "prep"))
    from hyperspace_tpu.train.logging import read_jsonl

    recs = read_jsonl(str(tmp_path / "run1.jsonl"))
    events = [r["event"] for r in recs if "event" in r]
    assert events == ["run_manifest", "startup", "telemetry_summary"]
    st = next(r for r in recs if r.get("event") == "startup")
    for name in ("pre_program", "import", "make_dataset", "load_graph",
                 "read_csv", "prep", "init", "place", "jit_trace",
                 "jit_lower", "compile", "dispatch"):
        assert st[f"startup/{name}_n"] >= 1, name
        assert st[f"startup/{name}_s"] >= st[f"startup/{name}_self_s"] >= 0
    assert st["startup/origin"] == "process_start"
    assert st["startup/dropped"] == 0
    assert st["startup/read_csv_n"] == 3     # edges, features, labels
    selfs = sum(v for k, v in st.items() if k.endswith("_self_s"))
    assert selfs + st["startup/unattributed_s"] == pytest.approx(
        st["startup/total_s"], abs=1e-4)
    assert st["startup/total_s"] > st["startup/import_self_s"] > 0
    assert st["startup/ctr/jax/recompiles"] >= st["startup/compile_n"] > 0
    assert st["startup/ctr/prep_cache/miss"] >= 1
    summary = recs[-1]
    assert {k: v for k, v in summary.items()
            if k.startswith("startup/")} == {
        k: v for k, v in st.items() if k.startswith("startup/")}
    # the second run of the process: no second start-up
    again = read_jsonl(str(tmp_path / "run2.jsonl"))
    assert [r["event"] for r in again if "event" in r] == [
        "run_manifest", "telemetry_summary"]
    assert not any(k.startswith("startup/") for k in again[-1])
    spans = json.loads(out.stdout.splitlines()[-1])
    preps = [s["args"] for s in spans if s["name"] == "prep"]
    assert preps and all(isinstance(a["kind"], str) and a["hit"] is False
                         for a in preps)
    csvs = {s["args"]["file"]: s["args"] for s in spans
            if s["name"] == "read_csv"}
    assert set(csvs) == {"edge.csv", "node-feat.csv", "node-label.csv"}
    assert csvs["node-feat.csv"]["rows"] == 1500
    assert csvs["node-feat.csv"]["bytes"] > 0
    load = next(s for s in spans if s["name"] == "load_graph")
    assert load["args"] == {"dataset": "ogbn-arxiv", "source": "disk"}


def _startup_script():
    spec = importlib.util.spec_from_file_location(
        "startup_report", os.path.join(REPO, "scripts", "startup_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_benchmarks_traced_run_keeps_the_timeline(
        timeline, tmp_path, monkeypatch, capsys):
    """The benchmark's tiny traced run through scripts/startup_report.py:
    the twin's build and the reference, after the window in the same
    process, leave the report as it stood at the close; the window's
    ``host_spans.json`` leads with the start-up spans, all of them over
    before the window's first step."""
    from hyperspace_tpu import compile_cache

    root = helpers.tiny_root(tmp_path)
    helpers.lift_refusals(monkeypatch)
    cell = "hgcn_arxiv_lp.fullgraph"
    out_file = str(tmp_path / "spans.json")
    try:
        rc = _startup_script().main(
            ["--workload", cell, "--seed", str(2**31 + 5), "--seconds",
             "0.5", "--trace", "1", "--out", out_file], root=root)
    finally:
        compile_cache.deactivate()
        trace.disable()
    assert rc == 0
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1])["correct"] is True
    told = json.loads(err.strip().splitlines()[-1])
    assert told["unchanged_after_window"] is True
    rep = told["startup_report"]
    assert rep == json.loads(json.dumps(trace.startup_report()))
    # (no ``prep``: at this size ``graph_cache=auto`` caches nothing)
    for name in ("load_graph", "read_csv", "init", "place", "compile",
                 "dispatch"):
        assert rep["spans"][name]["n"] >= 1, name
    assert abs(_parts(rep) - rep["total_s"]) < 1e-6
    assert set(told["compile"]) <= {"hit", "miss", "off"}
    with open(out_file) as f:
        assert len(json.load(f)) == len(timeline.records)

    chrome = os.path.join(root, ".cache", "benchmark", "trace", cell,
                          "host_spans.json")
    with open(chrome) as f:
        evs = json.load(f)["traceEvents"]
    assert evs[0]["name"] == "startup" and evs[1]["name"] == "pre_program"
    # the whole timeline leads (the tracer of this later, traced run held
    # none of it), and is over before the window's first step
    lead = evs[:len(timeline.records)]
    closed = evs[0]["ts"] + evs[0]["dur"]
    assert all(e["ts"] + e["dur"] <= closed + 1e-3 for e in lead)
    assert {"init", "place", "load_graph", "compile"} <= {
        e["name"] for e in lead}
    window = [e for e in evs[len(lead):] if e["name"] == "train_step"]
    assert window and all(e["ts"] >= closed for e in evs[len(lead):])
