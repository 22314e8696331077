"""The benchmark's one entry:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it loads, warms up, measures for ``--seconds`` and prints
one JSON object as the last line of its standard output.  Everything
that belongs to one cell is found by name from ``BENCHMARK.json``:

    benchmark/configs/<config>.json         the configuration as it is run
    benchmark/traffic/<traffic>.json        the traffic's parameters; names its driver
    benchmark/drivers/<driver>.py           ``run(ctx) -> dict``
    benchmark/limits/<cell>.json            the limits of ``correct``
    benchmark/layer_metrics/<metric>.json   names its reader and the reader's parameters
    benchmark/readers/<reader>.py           ``read(red, facts, peaks, spec) -> float | None``

so a later cell, configuration, traffic mix, driver or per-layer metric
is new files and new entries, never an edit here.  No chip, no run: a
platform other than ``tpu``, fewer or more devices than the cell asks
for, or a device kind without published peaks ends the run with a code
other than 0 and no result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXIT_REFUSED = 3


class Refusal(SystemExit):
    def __init__(self, why: str):
        print(f"benchmark: refused: {why}", file=sys.stderr)
        super().__init__(EXIT_REFUSED)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "_bench_" + "_".join(path[:-3].split(os.sep)[-2:])
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def find_cell(manifest: dict, workload: str) -> tuple:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refusal(f"no workload {workload!r} in BENCHMARK.json "
                      f"(known: {sorted(cells)})")
    cell = cells[workload]
    config = next(c for c in manifest["configs"]
                  if c["name"] == cell["config"])
    return cell, config


def metrics_of_cell(entries, cell_name: str) -> list:
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def read_layer_metrics(root: str, entries, red, facts, peaks) -> dict:
    out = {}
    for m in entries:
        spec = load_json(os.path.join(root, "benchmark", "layer_metrics",
                                      m["name"] + ".json"))
        reader = load_module(os.path.join(root, "benchmark", "readers",
                                          spec["reader"] + ".py"))
        value = reader.read(red, facts, peaks, spec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if root not in sys.path:
        sys.path.insert(0, root)
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cell, config_entry = find_cell(manifest, args.workload)
    if importlib.util.find_spec("hyperspace_tpu") is None:
        raise Refusal("the program (hyperspace_tpu) is not in this checkout")
    from benchmark import correctness, guards, trace_reduce

    try:
        device = guards.require_chip(int(cell["chips"]))
    except guards.Refused as e:
        raise Refusal(str(e)) from None
    from benchmark.peaks import device_peaks

    peaks = device_peaks(device["kind"])
    traffic = load_json(os.path.join(root, "benchmark", "traffic",
                                     cell["traffic"] + ".json"))
    ctx = types.SimpleNamespace(
        workload=cell["name"], chips=int(cell["chips"]),
        config=load_json(os.path.join(root, config_entry["file"])),
        traffic=traffic,
        limits=load_json(os.path.join(root, "benchmark", "limits",
                                      cell["name"] + ".json"))["limits"],
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START, root=root, peaks=peaks,
        cache_root=os.path.join(root, ".cache", "benchmark"))
    driver = load_module(os.path.join(root, "benchmark", "drivers",
                                      traffic["driver"] + ".py"))
    try:
        res = driver.run(ctx)
    except guards.Refused as e:
        raise Refusal(str(e)) from None

    device["memory_peak_bytes"] = int(res["memory_peak_bytes"])
    # the readings the peak was made from, beside it
    device.update({k: int(v) for k, v in res.get("memory", {}).items()})
    out = {"correct": correctness.all_within(res["checks"]),
           "attempted": int(res["attempted"]), "failed": int(res["failed"])}
    if args.trace:
        red = res["trace"]
        if not red.devices:
            raise Refusal("the trace holds no device plane")
        out["metrics"] = read_layer_metrics(
            root, metrics_of_cell(manifest["per_layer"], cell["name"]),
            red, res["facts"], peaks)
        busy = [trace_reduce.busy_ns(d) for d in red.devices]
        device["busy_s"] = sum(busy) / len(busy) * 1e-9
        device["window_s"] = red.window_ns * 1e-9
        out["breakdown"] = trace_reduce.breakdown(red)
    else:
        out["metrics"] = {
            m["name"]: {"value": float(res["end_to_end"][m["name"]]),
                        "unit": m["unit"]}
            for m in metrics_of_cell(manifest["end_to_end"], cell["name"])
            if m["name"] in res["end_to_end"]}
    out["device"] = device
    out["checks"] = {name: {"value": value if value == value else None,
                            "limit": limit}
                     for name, value, limit in res["checks"]}
    sys.stdout.flush()
    for name, value in res.get("info", {}).items():
        print(f"info {name}: {value:.6g}", file=sys.stderr)
    for name, value, limit in res["checks"]:
        print(f"check {name}: {value:.6g} (limit {limit:.6g})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
