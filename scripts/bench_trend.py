#!/usr/bin/env python
"""Cross-round bench trend reports + a regression gate.

Reads ``BENCH_r<N>.json`` round wrappers ({n, cmd, rc, tail, parsed})
from a directory, and ``bench_full.json`` (the bare result record every
local ``bench.py`` run rewrites).  The repo keeps no such wrappers any
more — the ones taken before PR 1 were deleted with PR 21, and
``PERF_LEDGER.jsonl`` is the record from the benchmark PR on — so the
script works on whatever directory ``--dir`` names (the tests write
their own).  What it gives:

- **trend table** (markdown to stdout by default; ``--json`` for the
  machine-readable form; ``--out-json``/``--out-md`` write files):
  per-round status (rc, parseable), the headline metric series with
  best-so-far, and every numeric detail key seen in ≥2 parseable
  rounds;
- **regression gate** (``--gate``): exits nonzero when the LATEST
  parseable value of any headline metric is more than ``--threshold``
  (default 10%) worse than the best parseable round's — the check a
  perf PR runs before shipping, instead of eyeballing.

Unparseable rounds (rc=0 with ``parsed: null``, rc=124 with nothing)
are listed, never fatal:
a lost artifact must not hide the rounds around it.  Sentinel records
(``metric`` of ``error`` / ``budget_exhausted``) appear in the rounds
table but are excluded from series and gate — a watchdog's value=0 is
an incident marker, not a measurement.  The same holds for a
**budget-exhausted primary**: a record whose metric is real but whose
``detail.budget_exhausted`` is set was cut short by the watchdog (the
checked-in 1-second-budget ``bench_full.json`` test artifact is the
standing example) — its numbers are partial, so it is a rounds row but
never a series point or gate candidate.

Better/worse per metric is inferred from the name (queries/s and
samples/s up, seconds and milliseconds down — ``direction()``);
unrecognized metrics are reported but never gated.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Optional

DEFAULT_THRESHOLD = 0.10

# sentinel records a failed/overran run emits in place of a measurement
SENTINEL_METRICS = {"error", "budget_exhausted"}

# detail subtrees that are not cross-round comparable: telemetry is
# process-cumulative (warmup-diluted, run-order dependent), tracebacks
# are text
_SKIP_DETAIL_KEYS = {"telemetry", "traceback"}

_HIGHER_TOKENS = ("per_s", "per_sec", "qps", "samples", "speedup",
                  "recall", "rate", "auc", "frac", "roofline", "ratio",
                  # the r19 pod-scaling leg: scaling_efficiency (fleet
                  # throughput over N× single-process) — closer to
                  # linear is better; its multihost_ok verdict is a
                  # JSON bool and therefore never a gated series at all
                  "scaling", "efficiency")
_LOWER_TOKENS = ("time", "stall", "waste", "recompile", "epoch_s",
                 "compile", "latency", "ttfq")
# lower-better tokens that outrank the higher-better list: "ratio" is
# generically higher-better (fused/unfused speedup ratios), but a
# waste ratio is still waste; "rate" is generically higher-better
# (cache_hit_rate, qps_at_recall...), but the r13 HTTP front door's
# shed_rate / deadline_rate are failure fractions — shedding MORE is
# never an improvement (latency itself — http_p99_ms and every
# latency_ms leaf — is already lower-better via the _ms suffix);
# "overhead" likewise (the r16 observability overhead_ratio is a cost
# fraction — a bigger ratio is a slower instrumented server); the r18
# live-index freshness/staleness family is a cost too — time-to-visible
# (``upsert_visible_ms``), stale answers served (``stale_results``) —
# growing fresher-slower or staler is never an improvement; the r20
# multi-tenant leg's ``tenant_fairness`` (starved p99 over solo p99 —
# a contention-damage RATIO, so it must outrank the generic ratio
# token) and the ``starved_p99_ms`` reading behind it are both costs —
# a tenant getting more starved is never an improvement
_LOWER_PRIORITY_TOKENS = ("waste", "shed", "deadline", "overhead",
                          "fresh", "stale", "visible", "fairness",
                          "starved")
# size tokens, matched per dotted-path SEGMENT (word-boundary style: the
# segment is the token, or carries it as a ``_``-separated word) so the
# r15 big-table leg's capacity metrics — ``table_mb.int8``,
# ``table_bytes``, ``hbm_gb`` — gate lower-is-better: a table growing
# is never an improvement.  Segment matching keeps substrings inert
# ("poincare_embed..." contains "mb" but carries no ``mb`` word; plain
# substring matching would have re-directioned every *embed* metric).
# Checked AFTER the higher-better tokens: a size word does not demote a
# metric that is explicitly a quality/throughput reading — the roofline
# FRACTION ``frac_hbm_roofline`` carries the hbm word but measures how
# close to the hbm roofline the step runs (higher is better)
_LOWER_SIZE_TOKENS = ("bytes", "mb", "hbm")
_LOWER_SUFFIXES = ("_s", "_ms", "_bytes")
# leaves that are the size of a measurement's basis, not a measurement
# — fewer samples is not an improvement
_NEUTRAL_LEAVES = {"n", "count"}
# workload-shape/config leaves: constants of the run, not measurements
# — a series that can never trend is table noise, dropped entirely
_CONFIG_LEAVES = {"devices", "num_nodes", "num_edges", "num_edges_padded",
                  "num_pairs", "batch_size", "steps", "steps_per_epoch",
                  "dim", "k"}


def _size_token(key: str) -> bool:
    """True when any dotted segment carries a ``_LOWER_SIZE_TOKENS``
    word: the segment IS the token, or holds it as an underscore-
    separated word (``table_mb``, ``hbm_gb``, ``bytes_f32``)."""
    for seg in key.split("."):
        words = seg.split("_")
        if any(t in words for t in _LOWER_SIZE_TOKENS):
            return True
    return False


def direction(key: str) -> Optional[str]:
    """'higher' / 'lower' = which way is better; None = unknown (shown,
    never gated).  Higher-better tokens win first: ``samples_per_s``
    ends in ``_s`` but is a throughput.  Suffixes are matched per
    dotted segment so nested detail paths keep their unit's direction
    (``detail.latency_ms.b8.p99`` is a millisecond metric even though
    the full path ends in ``.p99``) — except sample-count leaves
    (``...latency_ms.b8.n``), which have no better direction at all."""
    k = key.lower()
    if k.rsplit(".", 1)[-1] in _NEUTRAL_LEAVES:
        return None
    if "during_rollover" in k:
        # a ``*_during_rollover`` reading inherits its base metric's
        # direction (r18 live-index leg): ``p99_during_rollover_ms``
        # is still a latency, a ``qps_during_rollover`` would still be
        # a throughput — the window qualifier carries no direction
        return direction(re.sub(r"_?during_rollover", "", k))
    if any(t in k for t in _LOWER_PRIORITY_TOKENS):
        return "lower"
    if any(t in k for t in _HIGHER_TOKENS):
        return "higher"
    if _size_token(k):
        # table-capacity metrics (the r15 beyond-HBM leg): bytes / mb /
        # hbm gate lower-is-better — ``table_mb`` growing can never
        # read as an improvement
        return "lower"
    if (any(seg.endswith(_LOWER_SUFFIXES) for seg in k.split("."))
            or any(t in k for t in _LOWER_TOKENS)):
        return "lower"
    return None


def _round_sort_key(label: str) -> tuple:
    m = re.search(r"(\d+)", label)
    # numbered driver rounds first in order; 'full' (the working-copy
    # bench_full.json) sorts last = most recent
    return (0, int(m.group(1))) if m else (1, 0)


def load_rounds(root: str) -> list[dict]:
    """One row per artifact: round label, rc, whether it parsed, and
    the parsed result record (None for the lost rounds)."""
    rounds = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        label = os.path.basename(path)[len("BENCH_"):-len(".json")]
        row = {"round": label, "path": os.path.basename(path),
               "rc": None, "parsed": False, "record": None}
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
            row["error"] = f"{type(e).__name__}: {e}"
            rounds.append(row)
            continue
        if not isinstance(doc, dict):
            row["error"] = "not a wrapper object"
            rounds.append(row)
            continue
        row["rc"] = doc.get("rc")
        rec = doc.get("parsed")
        if isinstance(rec, dict) and "metric" in rec:
            row["parsed"] = True
            row["record"] = rec
        rounds.append(row)
    full = os.path.join(root, "bench_full.json")
    if os.path.exists(full):
        row = {"round": "full", "path": "bench_full.json", "rc": None,
               "parsed": False, "record": None}
        try:
            with open(full, encoding="utf-8") as f:
                rec = json.load(f)
            if isinstance(rec, dict) and "metric" in rec:
                row["parsed"] = True
                row["record"] = rec
        except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
            row["error"] = f"{type(e).__name__}: {e}"
        rounds.append(row)
    rounds.sort(key=lambda r: _round_sort_key(r["round"]))
    return rounds


def _flatten_numeric(tree, prefix: str = "", depth: int = 0) -> dict:
    """{dotted.path: number} over a detail dict's numeric scalar leaves
    (bools excluded — flags are config, not measurements)."""
    out: dict = {}
    if depth > 4 or not isinstance(tree, dict):
        return out
    for k, v in tree.items():
        if k in _SKIP_DETAIL_KEYS:
            continue
        path = f"{prefix}{k}"
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            if k in _CONFIG_LEAVES:
                continue
            out[path] = v
        elif isinstance(v, dict):
            out.update(_flatten_numeric(v, path + ".", depth + 1))
    return out


def build_series(rounds: list[dict]) -> dict:
    """Per-metric series over the parseable rounds.

    Headline series are keyed by the metric name itself; detail leaves
    by ``detail.<dotted.path>``.  Detail series need ≥2 points to be a
    trend; headline series are kept even as single points (the gate
    just has nothing to compare them to)."""
    headline: dict[str, list] = {}
    detail: dict[str, list] = {}
    for row in rounds:
        rec = row["record"]
        if not rec:
            continue
        metric = rec.get("metric")
        if metric in SENTINEL_METRICS or not metric:
            continue
        det = rec.get("detail")
        if isinstance(det, dict) and det.get("budget_exhausted"):
            # a watchdog-cut partial artifact (real metric, truncated
            # legs): a rounds-table row, never a series point — it must
            # not gate as the 'full' round nor set a phantom best
            continue
        value = rec.get("value")
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            headline.setdefault(metric, []).append(
                {"round": row["round"], "value": value,
                 "unit": rec.get("unit", "")})
        for path, v in _flatten_numeric(rec.get("detail") or {},
                                        "detail.").items():
            detail.setdefault(path, []).append(
                {"round": row["round"], "value": v})
    series = {}
    for key, pts in headline.items():
        series[key] = _summarize(key, pts, headline=True)
    for key, pts in detail.items():
        if len(pts) >= 2:
            series[key] = _summarize(key, pts, headline=False)
    return series


def _summarize(key: str, pts: list[dict], *, headline: bool) -> dict:
    d = direction(key)
    best = None
    if d is not None:
        pick = max if d == "higher" else min
        best = pick(pts, key=lambda p: p["value"])
    latest = pts[-1]
    out = {"direction": d, "points": pts, "latest": latest,
           "headline": headline}
    if best is not None:
        out["best"] = best
        if best["value"]:
            delta = (latest["value"] - best["value"]) / abs(best["value"])
            # signed relative move of latest vs best; for lower-better
            # metrics a POSITIVE delta is the regression direction
            out["latest_vs_best_pct"] = round(delta * 100, 2)
    return out


def gate(series: dict, threshold: float) -> dict:
    """Regressions among the HEADLINE series: latest parseable value
    more than ``threshold`` worse than best-so-far."""
    regressions = []
    for key, s in series.items():
        if not s.get("headline") or "best" not in s:
            continue
        best, latest = s["best"], s["latest"]
        if latest["round"] == best["round"]:
            continue
        if best["value"]:
            rel = (latest["value"] - best["value"]) / abs(best["value"])
            worse = -rel if s["direction"] == "higher" else rel
            pct = round(worse * 100, 2)
            tripped = worse > threshold
        else:
            # best == 0: the relative move is unbounded, so ANY step in
            # the regression direction trips the gate (pct unreportable)
            diff = latest["value"] - best["value"]
            worse = -diff if s["direction"] == "higher" else diff
            pct = None
            tripped = worse > 0
        if tripped:
            regressions.append({
                "metric": key,
                "best": best, "latest": latest,
                "regression_pct": pct,
            })
    return {"threshold_pct": round(threshold * 100, 2),
            "regressions": regressions, "ok": not regressions}


def build_report(root: str, threshold: float) -> dict:
    rounds = load_rounds(root)
    series = build_series(rounds)
    def _cut_short(rec) -> bool:
        det = (rec or {}).get("detail")
        return bool(isinstance(det, dict) and det.get("budget_exhausted"))

    public_rounds = [{k: v for k, v in r.items() if k != "record"}
                     | {"metric": (r["record"] or {}).get("metric"),
                        "value": (r["record"] or {}).get("value"),
                        "budget_exhausted": _cut_short(r["record"])}
                     for r in rounds]
    return {"rounds": public_rounds, "series": series,
            "gate": gate(series, threshold)}


def _fmt(v) -> str:
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:g}"
    return str(v)


def _pct(p) -> str:
    # None = regression from a zero best: relative move is unbounded
    return "inf" if p is None else f"{p:g}"


def to_markdown(report: dict) -> str:
    lines = ["# Bench trend", "", "## Rounds", "",
             "| round | rc | parsed | metric | value |",
             "|---|---|---|---|---|"]
    for r in report["rounds"]:
        lines.append(
            f"| {r['round']} | {_fmt(r['rc'])} | "
            f"{'yes' if r['parsed'] else 'NO'} | "
            f"{_fmt(r.get('metric'))} | {_fmt(r.get('value'))} |")
    head = {k: s for k, s in report["series"].items() if s["headline"]}
    lines += ["", "## Headline metrics", "",
              "| metric | better | best (round) | latest (round) "
              "| latest vs best |", "|---|---|---|---|---|"]
    for key in sorted(head):
        s = head[key]
        best = s.get("best")
        pct = s.get("latest_vs_best_pct")
        lines.append(
            f"| {key} | {_fmt(s['direction'])} | "
            + (f"{_fmt(best['value'])} ({best['round']})"
               if best else "—")
            + f" | {_fmt(s['latest']['value'])} ({s['latest']['round']})"
            + f" | {'—' if pct is None else f'{pct:+g}%'} |")
    tail = {k: s for k, s in report["series"].items()
            if not s["headline"]}
    if tail:
        lines += ["", "## Detail series (≥2 rounds)", "",
                  "| key | better | best (round) | latest (round) |",
                  "|---|---|---|---|"]
        for key in sorted(tail):
            s = tail[key]
            best = s.get("best")
            lines.append(
                f"| {key} | {_fmt(s['direction'])} | "
                + (f"{_fmt(best['value'])} ({best['round']})"
                   if best else "—")
                + f" | {_fmt(s['latest']['value'])}"
                  f" ({s['latest']['round']}) |")
    g = report["gate"]
    lines += ["", "## Gate", ""]
    if g["regressions"]:
        lines.append(f"**{len(g['regressions'])} regression(s) past "
                     f"{g['threshold_pct']:g}%:**")
        for r in g["regressions"]:
            lines.append(
                f"- `{r['metric']}`: {_fmt(r['latest']['value'])} "
                f"({r['latest']['round']}) is {_pct(r['regression_pct'])}% "
                f"worse than best {_fmt(r['best']['value'])} "
                f"({r['best']['round']})")
    else:
        lines.append(f"No headline regression past "
                     f"{g['threshold_pct']:g}% vs best-so-far.")
    return "\n".join(lines) + "\n"


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_trend",
        description="Aggregate BENCH_r*.json + bench_full.json into a "
                    "per-metric trend table; --gate fails on a "
                    "regression vs the best parseable round.")
    ap.add_argument("--dir", default=None,
                    help="repo root holding the artifacts "
                         "(default: this script's parent repo)")
    ap.add_argument("--json", action="store_true",
                    help="print the JSON report to stdout instead of "
                         "markdown")
    ap.add_argument("--out-json", default=None,
                    help="also write the JSON report to this path")
    ap.add_argument("--out-md", default=None,
                    help="also write the markdown report to this path")
    ap.add_argument("--gate", action="store_true",
                    help="exit 1 when any headline metric's latest "
                         "parseable value is > threshold worse than the "
                         "best round's")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="gate threshold as a fraction (default 0.10)")
    args = ap.parse_args(argv)

    root = args.dir or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    report = build_report(root, args.threshold)
    if not report["rounds"]:
        print(f"no BENCH_r*.json / bench_full.json under {root}",
              file=sys.stderr)
        return 2

    if args.out_json:
        with open(args.out_json, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=2)
    if args.out_md:
        with open(args.out_md, "w", encoding="utf-8") as f:
            f.write(to_markdown(report))
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(to_markdown(report), end="")
    if args.gate:
        g = report["gate"]
        for r in g["regressions"]:
            print(f"GATE: {r['metric']} regressed "
                  f"{_pct(r['regression_pct'])}% vs {r['best']['round']}",
                  file=sys.stderr)
        if not g["ok"]:
            return 1
        print(f"GATE: ok ({g['threshold_pct']:g}% threshold)",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
