"""scripts/bench_trend.py: the cross-round trend report + regression
gate, run (1) against five round wrappers in the shapes driver rounds
have left behind — three parsed, one rc=0 with ``parsed: null``, one
rc=124 with nothing — which it must tolerate without crashing and still
gate green, and (2) against synthetic fixtures proving the gate's
pass/fail contract."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SCRIPT = os.path.join(ROOT, "scripts", "bench_trend.py")


def _run(*args, cwd=ROOT):
    return subprocess.run(
        [sys.executable, SCRIPT, *args],
        capture_output=True, text=True, cwd=cwd, timeout=60)


def _wrapper(n, value, metric="hgcn_samples_per_sec_per_chip", rc=0,
             detail=None):
    return {"n": n, "cmd": "python bench.py", "rc": rc, "tail": "…",
            "parsed": {"metric": metric, "value": value,
                       "unit": "samples/s/chip", "vs_baseline": None,
                       "detail": detail or (
                           {"step_time_s": 1.0 / value} if value else {})}}


def _write_rounds(tmp_path, values, metric="hgcn_samples_per_sec_per_chip"):
    for i, v in enumerate(values, 1):
        (tmp_path / f"BENCH_r{i:02d}.json").write_text(
            json.dumps(_wrapper(i, v, metric=metric)))


# --- rounds in the shapes real driver rounds took -----------------------------


def _stage_lossy_rounds(tmp_path) -> str:
    """Five wrappers: r01–r03 parsed with a rising headline and the
    nested detail a full bench run carries, r04 rc=0 whose output could
    not be parsed (the numbers survive only in its tail), r05 killed at
    the time limit (rc=124) with nothing."""
    cmd = "if [ -f bench.py ]; then python bench.py; else exit 0; fi"
    for n, (value, step_s) in enumerate(
            [(797233.4, 0.21241), (991760.2, 0.17075),
             (1244134.8, 0.13611)], 1):
        detail = {"num_nodes": 169343, "num_edges_padded": 2424832,
                  "steps": 10, "step_time_s": step_s, "loss": 0.66,
                  "devices": 1, "backend": "tpu", "source": "synthetic",
                  "dtype": "float32", "use_att": False,
                  "poincare_embed_epoch_time_s": 0.21 - 0.01 * n,
                  "poincare": {"num_nodes": 66430, "batch_size": 1024,
                               "dense_epoch_s": 0.20 - 0.004 * n},
                  "hgcn_sampled": {"step_ms": 1.9 - 0.01 * n,
                                   "fanouts": [10, 10]}}
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(
            {**_wrapper(n, value, detail=detail), "cmd": cmd}))
    (tmp_path / "BENCH_r04.json").write_text(json.dumps(
        {"n": 4, "cmd": cmd, "rc": 0, "parsed": None,
         "tail": '376404, "devices": 1, "backend": "tpu", "source": "syn'}))
    (tmp_path / "BENCH_r05.json").write_text(json.dumps(
        {"n": 5, "cmd": cmd, "rc": 124, "parsed": None, "tail": ""}))
    return str(tmp_path)


def test_real_artifacts_emit_parseable_trend_json(tmp_path):
    res = _run("--dir", _stage_lossy_rounds(tmp_path), "--json")
    assert res.returncode == 0, res.stderr
    report = json.loads(res.stdout)
    rounds = {r["round"]: r for r in report["rounds"]}
    # r01–r05 all listed; the two lost rounds are rows, not crashes
    for r in ("r01", "r02", "r03", "r04", "r05"):
        assert r in rounds
    assert rounds["r01"]["parsed"] and rounds["r03"]["parsed"]
    assert not rounds["r04"]["parsed"]          # rc=0, parsed null
    assert not rounds["r05"]["parsed"]          # rc=124, no artifact
    assert rounds["r05"]["rc"] == 124
    # the headline series exists with the known best
    s = report["series"]["hgcn_samples_per_sec_per_chip"]
    assert s["direction"] == "higher"
    assert s["best"]["value"] == 1244134.8 and s["best"]["round"] == "r03"
    # workload-shape constants never appear as detail series
    for noise in ("detail.num_nodes", "detail.devices", "detail.steps"):
        assert noise not in report["series"], noise


def test_real_artifacts_gate_green(tmp_path):
    res = _run("--dir", _stage_lossy_rounds(tmp_path), "--gate")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "GATE: ok" in res.stderr


def test_real_artifacts_markdown_mode(tmp_path):
    md_out = str(tmp_path / "trend.md")
    res = _run("--dir", _stage_lossy_rounds(tmp_path), "--out-md", md_out)
    assert res.returncode == 0, res.stderr
    md = open(md_out).read()
    assert "# Bench trend" in md and "r04" in md and "r05" in md
    assert md == res.stdout  # stdout default is the same markdown


# --- synthetic gate fixtures -------------------------------------------------


def test_gate_passes_on_improving_series(tmp_path):
    _write_rounds(tmp_path, [100.0, 110.0, 121.0])
    res = _run("--dir", str(tmp_path), "--gate")
    assert res.returncode == 0, res.stdout + res.stderr


def test_gate_fails_on_regression_past_threshold(tmp_path):
    # latest 95 vs best 110: 13.6% down on a higher-better metric
    _write_rounds(tmp_path, [100.0, 110.0, 95.0])
    res = _run("--dir", str(tmp_path), "--gate")
    assert res.returncode == 1
    assert "regressed" in res.stderr
    # a looser threshold lets the same series through
    res = _run("--dir", str(tmp_path), "--gate", "--threshold", "0.2")
    assert res.returncode == 0


def test_gate_respects_lower_better_direction(tmp_path):
    # epoch time growing 1.0 → 1.25 s is the regression direction
    _write_rounds(tmp_path, [1.0, 1.25],
                  metric="poincare_embed_epoch_time")
    res = _run("--dir", str(tmp_path), "--gate")
    assert res.returncode == 1
    _write_rounds(tmp_path, [1.25, 1.0],
                  metric="poincare_embed_epoch_time")
    assert _run("--dir", str(tmp_path), "--gate").returncode == 0


def test_gate_zero_best_still_gates(tmp_path):
    # a lower-better headline whose best round recorded exactly 0 must
    # not be exempt: any step away from 0 is an (unboundedly large)
    # regression — reported with regression_pct null, not skipped
    _write_rounds(tmp_path, [0.0, 50.0],
                  metric="poincare_embed_epoch_time")
    res = _run("--dir", str(tmp_path), "--gate", "--json")
    assert res.returncode == 1, res.stdout
    regs = json.loads(res.stdout)["gate"]["regressions"]
    assert [r["regression_pct"] for r in regs] == [None]
    # holding at 0 is not a regression
    _write_rounds(tmp_path, [0.0, 0.0],
                  metric="poincare_embed_epoch_time")
    assert _run("--dir", str(tmp_path), "--gate").returncode == 0


def test_nested_detail_ms_series_infer_lower_direction(tmp_path):
    # the dotted detail path ends in '.p99'/'.f32', but the unit lives
    # in the 'latency_ms'/'train_step_ms' segment — the series this PR
    # adds must get a direction, not the '—' column
    for i, (p99, step) in enumerate([(2.0, 700.0), (2.4, 650.0)], 1):
        detail = {"latency_ms": {"b8": {"n": 4, "p50": 1.0, "p99": p99}},
                  "precision": {"train_step_ms": {"f32": step}}}
        (tmp_path / f"BENCH_r{i:02d}.json").write_text(
            json.dumps(_wrapper(i, 100.0 + i, detail=detail)))
    res = _run("--dir", str(tmp_path), "--json")
    assert res.returncode == 0, res.stderr
    series = json.loads(res.stdout)["series"]
    for key in ("detail.latency_ms.b8.p99",
                "detail.precision.train_step_ms.f32"):
        assert series[key]["direction"] == "lower", key
        assert "best" in series[key]
    assert series["detail.latency_ms.b8.p99"]["best"]["value"] == 2.0
    # the sample-count leaf is basis size, not a measurement: never
    # ranked best-when-smallest
    assert series["detail.latency_ms.b8.n"]["direction"] is None


def test_gate_tolerates_lost_rounds_and_sentinels(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps(_wrapper(1, 100.0)))
    # the r04 loss mode: rc=0, parsed null
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"n": 2, "cmd": "python bench.py", "rc": 0, "tail": "garbage",
         "parsed": None}))
    # the r05 loss mode: driver timeout
    (tmp_path / "BENCH_r03.json").write_text(json.dumps(
        {"n": 3, "cmd": "python bench.py", "rc": 124, "tail": "",
         "parsed": None}))
    # a watchdog sentinel in bench_full.json must not gate (value 0!)
    (tmp_path / "bench_full.json").write_text(json.dumps(
        {"metric": "budget_exhausted", "value": 0, "unit": "",
         "vs_baseline": None, "detail": {"budget_exhausted": True}}))
    res = _run("--dir", str(tmp_path), "--gate", "--json")
    assert res.returncode == 0, res.stdout + res.stderr
    report = json.loads(res.stdout)
    assert len(report["rounds"]) == 4
    assert "budget_exhausted" not in report["series"]
    # the one parseable measurement survives as the series
    assert report["series"]["hgcn_samples_per_sec_per_chip"][
        "latest"]["value"] == 100.0


def test_bench_full_participates_as_latest_round(tmp_path):
    _write_rounds(tmp_path, [100.0, 110.0])
    # a fresh local bench run regressing 20% must trip the gate even
    # before a driver round records it
    (tmp_path / "bench_full.json").write_text(json.dumps(
        _wrapper(0, 88.0)["parsed"]))
    res = _run("--dir", str(tmp_path), "--gate", "--json")
    assert res.returncode == 1
    report = json.loads(res.stdout)
    s = report["series"]["hgcn_samples_per_sec_per_chip"]
    assert s["latest"]["round"] == "full"
    assert report["gate"]["regressions"][0]["regression_pct"] > 10


def test_empty_dir_is_a_distinct_error(tmp_path):
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 2
    assert "no BENCH_r*" in res.stderr


def test_unreadable_round_is_a_row_not_a_crash(tmp_path):
    _write_rounds(tmp_path, [100.0])
    (tmp_path / "BENCH_r02.json").write_text("{not json")
    res = _run("--dir", str(tmp_path), "--json")
    assert res.returncode == 0, res.stderr
    rounds = {r["round"]: r for r in json.loads(res.stdout)["rounds"]}
    assert not rounds["r02"]["parsed"] and "error" in rounds["r02"]


def test_direction_quality_metrics_are_higher_better():
    """Names carrying recall / hit_rate / auc are higher-is-better —
    the r10 recall@10 contract (and any future quality series) must
    gate in the right direction, not fall into the `_s`-suffix
    lower-better bucket or the unknown `—` column."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_trend", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for name in ("recall10", "detail.ivf.probes.np4.recall10",
                 "detail.serve.ivf.qps_at_recall99",
                 "detail.serve.cache.cache_hit_rate", "val_auc",
                 "detail.hgcn.roc_auc"):
        assert mod.direction(name) == "higher", name
    # and the lower-better inference stays undisturbed around them
    assert mod.direction("detail.serve.ivf.build_s") == "lower"
    assert mod.direction("detail.latency_ms.b8.p99") == "lower"


def test_direction_speedup_ratio_are_higher_better():
    """Names carrying speedup / ratio are higher-is-better — the r12
    serve_fused_speedup headline and the per-bucket fused/two_stage
    ratios must gate in the right direction from round one.  The one
    exception: a *waste* ratio stays lower-better (waste outranks the
    generic ratio token)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_trend", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for name in ("serve_fused_speedup",
                 "detail.serve.fused_vs_unfused.serve_fused_speedup",
                 "detail.serve.fused_vs_unfused.buckets.b64.ratio",
                 "speedup_at_recall99"):
        assert mod.direction(name) == "higher", name
    assert mod.direction("detail.serve.cache.padded_waste_ratio") == "lower"


def test_direction_table_size_tokens_are_lower_better():
    """The r15 big-table leg's capacity metrics — bytes / mb / hbm
    word-tokens per dotted segment — gate lower-is-better: a table
    growing must never read as regressions-are-good.  Matching is
    word-boundary per segment, so substrings stay inert: every *embed*
    metric contains the letters "mb" and must keep its own direction."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_trend", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for name in ("table_bytes", "detail.big_table.table_mb.int8",
                 "detail.big_table.table_mb.f32", "hbm_gb",
                 "detail.big_table.lanes.bf16.table_mb",
                 "detail.big_table.hbm_bytes",
                 # the r16 sub-int8 lanes' compact fields and nested
                 # paths gate the same way — smaller tables only
                 "big_table_mb_int4", "big_table_mb_pq",
                 "detail.big_table.table_mb.pq",
                 "detail.big_table.lanes.int4.table_mb",
                 "detail.big_table.lanes.pq.table_mb"):
        assert mod.direction(name) == "lower", name
    # …while the lanes' per-probe recall stays a quality reading
    assert mod.direction(
        "detail.big_table.lanes.pq.probes.np8.recall10") == "higher"
    # substring immunity: "embed" carries no mb *word*
    assert mod.direction("poincare_embed_epoch_time") == "lower"  # time
    assert mod.direction("detail.poincare.embed_samples_per_s") == "higher"
    # and the size tokens never capture unrelated neighbors — nor
    # demote explicit quality/throughput readings that carry a size
    # word: the roofline FRACTION stays higher-better
    assert mod.direction("detail.big_table.qps_at_recall99.int8") == "higher"
    assert mod.direction("frac_hbm_roofline") == "higher"
    assert mod.direction("detail.big_table.lanes.int8.n") is None


def test_direction_freshness_staleness_are_lower_better():
    """The r18 live-index leg's freshness/staleness family is a cost:
    time-to-visible after an upsert, stale answers served, tombstones
    outstanding — growing any of them is never an improvement.  The
    tokens outrank the generic higher-better list the same way shed /
    deadline do (a stale *rate* is still staleness)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_trend", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for name in ("upsert_visible_ms",
                 "detail.live_index.freshness.upsert_visible_ms.p99",
                 "detail.live_index.stale_results", "stale_rate",
                 "detail.live_index.staleness_ms"):
        assert mod.direction(name) == "lower", name


def test_direction_fairness_starvation_are_lower_better():
    """The r20 multi-tenant leg's fairness family is a cost: the
    ``tenant_fairness`` ratio is starved-p99 over solo-p99 (contention
    damage — it must outrank the generic higher-better ratio token the
    same way waste_ratio does) and ``starved_p99_ms`` is the latency
    behind it.  The aggregate throughput at the tenant mix stays
    higher-better via the qps token."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_trend", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for name in ("tenant_fairness", "detail.multitenant.fairness",
                 "fairness_ratio", "starved_p99_ms",
                 "detail.multitenant.starved_p99_ms"):
        assert mod.direction(name) == "lower", name
    assert mod.direction("multitenant_agg_qps") == "higher"
    assert mod.direction(
        "detail.multitenant.aggregate_qps") == "higher"


def test_direction_during_rollover_inherits_base_metric():
    """``*_during_rollover`` readings (r18) inherit the base metric's
    direction: the window qualifier carries none of its own.  A p99
    latency across the flip stays lower-better, a throughput measured
    across the flip would stay higher-better — and the bare qualifier
    resolves to no direction at all (shown, never gated)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_trend", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for name in ("p99_during_rollover_ms",
                 "detail.live_index.p99_during_rollover_ms",
                 "recompiles_during_rollover"):
        assert mod.direction(name) == "lower", name
    assert mod.direction("qps_during_rollover") == "higher"
    assert mod.direction(
        "detail.live_index.recall_during_rollover") == "higher"
    assert mod.direction("during_rollover") is None


def test_direction_scaling_efficiency_is_higher_better():
    """The r19 pod-scaling leg: ``scaling_efficiency`` (2-proc fleet
    throughput over 2× 1-proc) gates higher-is-better — drifting away
    from linear scaling is the regression.  Its ``multihost_ok``
    verdict is a JSON bool, and bools are excluded at flatten time
    (flags are config, not measurements), so the verdict can never
    become a gated series."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_trend", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for name in ("multihost_scaling_efficiency",
                 "detail.multihost.scaling_efficiency",
                 "scaling_efficiency"):
        assert mod.direction(name) == "higher", name
    # per-proc-count throughput rows keep their own directions
    assert mod.direction(
        "detail.multihost.procs.2.steps_per_s") == "higher"
    assert mod.direction(
        "detail.multihost.procs.2.step_time_s") == "lower"
    flat = mod._flatten_numeric(
        {"multihost_ok": True, "scaling_efficiency": 0.5})
    assert "scaling_efficiency" in flat and "multihost_ok" not in flat


def test_budget_exhausted_primary_never_gates(tmp_path):
    """A record whose metric is real but whose detail carries
    budget_exhausted (the watchdog's partial artifact — the checked-in
    1-second-budget bench_full.json class) is a rounds row, never a
    series point: it must not gate as the 'full' round nor set a
    phantom best."""
    _write_rounds(tmp_path, [100.0, 110.0])
    rec = _wrapper(0, 50.0)["parsed"]  # a 55% "regression"…
    rec["detail"]["budget_exhausted"] = True  # …from a cut-short run
    (tmp_path / "bench_full.json").write_text(json.dumps(rec))
    res = _run("--dir", str(tmp_path), "--gate", "--json")
    assert res.returncode == 0, res.stdout + res.stderr
    report = json.loads(res.stdout)
    rounds = {r["round"]: r for r in report["rounds"]}
    assert rounds["full"]["parsed"] and rounds["full"]["budget_exhausted"]
    s = report["series"]["hgcn_samples_per_sec_per_chip"]
    assert s["latest"]["round"] == "r02"  # the partial never entered
    # and a cut-short BEST is equally excluded: a lucky partial must
    # not raise the bar the honest rounds gate against
    rec["value"] = 500.0
    (tmp_path / "bench_full.json").write_text(json.dumps(rec))
    res = _run("--dir", str(tmp_path), "--gate", "--json")
    assert res.returncode == 0
    s = json.loads(res.stdout)["series"]["hgcn_samples_per_sec_per_chip"]
    assert s["best"]["value"] == 110.0


def test_direction_compile_and_ttfq_lower_better():
    """The r14 cold-start / compile-cache fields gate lower-is-better:
    cold_ttfq_ms at headline and nested paths, the compile counters,
    and every recompiles* token."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_trend", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for name in ("cold_ttfq_ms", "detail.cold_start.cold_ttfq_ms",
                 "detail.cold_start.warm_cache.ttfq_ms",
                 "detail.cold_start.cache_off.ttfq_ms",
                 "cold_recompiles_steady",
                 "detail.cold_start.warm_prewarm.recompiles_first",
                 "compile_s", "detail.serve.recompiles_warmup",
                 "recompiles_steady", "serve_recompiles_steady"):
        assert mod.direction(name) == "lower", name
    # neighbors keep their directions
    assert mod.direction("detail.serve.ivf.qps_at_recall99") == "higher"
    assert mod.direction("detail.cold_start.warm_prewarm.n") is None


def test_direction_http_front_door_fields_are_lower_better():
    """The r13 HTTP front-door compact fields gate in the right
    direction: http_p99_ms (latency) and shed_rate / deadline_rate
    (failure fractions — the "shed"/"deadline" tokens outrank the
    generically-higher-better "rate") are all lower-is-better, at the
    headline and at every nested detail path."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_trend", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for name in ("http_p99_ms", "detail.serve_http.http_p99_ms",
                 "serve_http_p99_ms",
                 "shed_rate", "http_shed_rate",
                 "detail.serve_http.shed_rate",
                 "detail.serve_http.deadline_rate",
                 "detail.serve_http.latency_ms.b8.p99",
                 "detail.serve_http.aggregate_ms.p99",
                 "detail.resilience.overload.shed_rate"):
        assert mod.direction(name) == "lower", name
    # the rate/ratio families around them keep their directions
    assert mod.direction("detail.serve.cache.cache_hit_rate") == "higher"
    assert mod.direction("serve_fused_speedup") == "higher"
    assert mod.direction("detail.serve.ivf.qps_at_recall99") == "higher"
    # sample-count leaves stay direction-free
    assert mod.direction("detail.serve_http.latency_ms.b8.n") is None


def test_direction_observability_overhead_is_lower_better():
    """The r16 observability pair: overhead_ratio is a COST fraction —
    'overhead' outranks the generic higher-better ratio token — and the
    paired p99 leaves keep their _ms lower-better direction."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_trend", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.direction(
        "detail.serve_http.observability.overhead_ratio") == "lower"
    assert mod.direction(
        "detail.serve_http.observability.p99_on_ms") == "lower"
    assert mod.direction(
        "detail.serve_http.observability.p99_off_ms") == "lower"
    # the generic speedup ratio direction is untouched
    assert mod.direction(
        "detail.serve.fused_vs_unfused.buckets.b64.ratio") == "higher"
