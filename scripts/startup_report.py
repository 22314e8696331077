#!/usr/bin/env python
"""One benchmark cell's start-up timeline, beside its result line:

    python3 scripts/startup_report.py --workload <cell> --seed <n> \
        --seconds <s> [--trace 0|1] [--out <file>]

Runs ``benchmark/run.py``'s ``main`` in this process, as the driver's
command does (the program is imported where the harness imports it, so
``pre_program`` holds what it holds in a benchmark run), and after the
result line prints one JSON line on standard error:

    {"startup_report": <telemetry.trace.startup_report()>,
     "unchanged_after_window": <the report as it stood after the window
                                (where the driver reads the device's
                                memory) equals the report at the
                                process's end: the twin's build and the
                                reference ran in between; null where the
                                driver has no such call>,
     "import_self_s": {module: seconds, ...the twelve largest...},
     "compile": {"hit" | "miss" | "off": {"n", "s"}},
     "slowest": [[name, detail, seconds], ...the twelve longest spans
                 other than imports, by self time...]}

``--out`` also writes every record (``trace.startup_spans()``) there.
Outside the benchmark's ``paths``: the benchmark reads none of this
(PERF.md §7, item 12, says what a ``benchmark`` issue would read).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

_TRACE_MODULE = "hyperspace_tpu.telemetry.trace"


def _read_after_window(found: dict):
    """Keep the report as it stands between the window and the twin's
    build.  The drivers read the device's memory there, through
    ``device_memory_peak`` (``train_fullgraph``'s own, the other two
    through ``one``): wrap that call where a driver is loaded.  No
    thread: one that polled ten times a second made the program's
    imports 7 s slower on the chip's machine (PERF.md §6, PR 37), and
    this script must not import the program before the harness does."""
    load = bench_run.load_module

    def load_module(path):
        mod = load(path)
        for holder in (mod, getattr(mod, "one", None)):
            read = getattr(holder, "device_memory_peak", None)
            if read is None or hasattr(read, "reads_startup"):
                continue

            def device_memory_peak(*a, _read=read, **kw):
                trace = sys.modules.get(_TRACE_MODULE)
                if trace is not None:
                    found["after_window"] = json.dumps(
                        trace.startup_report(), sort_keys=True)
                return _read(*a, **kw)

            device_memory_peak.reads_startup = True
            holder.device_memory_peak = device_memory_peak
        return mod

    bench_run.load_module = load_module
    return load


def summary(trace, after_window) -> dict:
    rep = trace.startup_report()
    imports, compiles, others = {}, {}, []
    for s in trace.startup_spans():
        args = s["args"] or {}
        if s["name"] == "import":
            imports[args["module"]] = round(s["self_s"], 4)
        elif s["name"] == "compile":
            c = compiles.setdefault(args["cache"], {"n": 0, "s": 0.0})
            c["n"] += 1
            c["s"] = round(c["s"] + s["dur_s"], 4)
        if s["name"] not in ("import", "startup"):
            detail = (args.get("fun_name") or args.get("file")
                      or args.get("kind") or args.get("model") or "")
            others.append([s["name"], detail, round(s["self_s"], 4)])
    top = lambda items, key: sorted(items, key=key, reverse=True)[:12]
    return {
        "startup_report": rep,
        "unchanged_after_window": (
            None if after_window is None
            else after_window == json.dumps(rep, sort_keys=True)),
        "import_self_s": dict(top(imports.items(), lambda kv: kv[1])),
        "compile": compiles,
        "slowest": top(others, lambda o: o[2])}


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(prog="scripts/startup_report.py")
    ap.add_argument("--out", default=None)
    args, bench_argv = ap.parse_known_args(argv)
    found = {}
    load = _read_after_window(found)
    try:
        rc = bench_run.main(bench_argv, root=root)
    finally:
        bench_run.load_module = load
    trace = sys.modules.get(_TRACE_MODULE)
    if trace is None or trace.startup_report() is None:
        print("startup_report: no closed start-up timeline in this "
              "process", file=sys.stderr)
        return rc or 1
    sys.stdout.flush()
    print(json.dumps(summary(trace, found.get("after_window"))),
          file=sys.stderr)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(trace.startup_spans(), f)
    return rc


if __name__ == "__main__":
    sys.exit(main())
