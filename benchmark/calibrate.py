"""Readings the limits of ``correct`` are set from, taken on the chip at
the cell's own size, many seeds in one process:

    python3 benchmark/calibrate.py --workload <cell> --seeds 12 --first-seed <n> [--controls 3]

For every seed: the program's first steps, and its twin's first step,
against the plain reference (the lower readings).  For the first
``--controls`` seeds also, each put in the program's place:

- ``control``: the reference one step down everywhere (float32 parts in
  bfloat16, the bf16 lanes' values stored in fp8; the twin, whose lanes
  are float32, in bfloat16 throughout);
- ``control_bf16_only``: the float32 parts alone in bfloat16, the stated
  lanes left as they are: what the twin's numbers are there to catch;
- ``fault_half_batch``: half of the pair batch left out, the mean taken
  over the rest.

Every one of them goes through the harness's own comparison
(``correctness.compare_training``) under the committed limits of the
cell, and the line says what ``correct`` came out as.  One JSON line a
seed; the last line sums up.  The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_000_000_011)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)

    import jax.numpy as jnp
    import numpy as np

    from benchmark import correctness, guards
    from benchmark import run as bench_run
    from benchmark.drivers import train_fullgraph as drv
    from benchmark.reference import hgcn as ref

    manifest = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, centry = bench_run.find_cell(manifest, args.workload)
    guards.require_chip(int(cell["chips"]))
    guards.require_pallas()
    config = bench_run.load_json(os.path.join(ROOT, centry["file"]))
    traffic = bench_run.load_json(os.path.join(
        ROOT, "benchmark", "traffic", cell["traffic"] + ".json"))
    limits = bench_run.load_json(os.path.join(
        ROOT, "benchmark", "limits", cell["name"] + ".json"))["limits"]
    from hyperspace_tpu import compile_cache

    compile_cache.activate(None)
    data_root = drv.ensure_dataset(
        config, os.path.join(ROOT, ".cache", "benchmark"))
    steps = int(traffic["check_steps"])
    twin_cfg = drv.twin_config(config)
    inputs = None
    worst: dict = {}
    verdicts: dict = {}

    def judge(kind, got, want, twin):
        checks = correctness.compare_training(got, want, limits, twin)
        ok = correctness.all_within(checks)
        for name, value, _ in checks:
            lo, hi = worst.get((kind, name), (float("inf"), 0.0))
            worst[(kind, name)] = (min(lo, value), max(hi, value))
        verdicts.setdefault(kind, []).append(ok)
        return {"correct": ok,
                "failed": [n for n, v, lim in checks if not v <= lim],
                "gaps": {n: v for n, v, _ in checks}}

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        built, start_tree, weights, key = drv.seeded_build(
            config, traffic, seed, data_root)
        _, got = drv.first_steps(built, start_tree, steps)
        order, train_pos = built.order, built.split.train_pos
        del built, start_tree
        gc.collect()
        got_twin = None
        if twin_cfg is not None:
            built, start_tree, _, _ = drv.seeded_build(
                twin_cfg, traffic, seed, data_root, (weights, key))
            _, got_twin = drv.first_steps(built, start_tree, 1)
            del built, start_tree
            gc.collect()
        if inputs is None:
            inputs = drv.reference_inputs(config, data_root, order,
                                          train_pos)
        x, graph = inputs

        def follow(compute="float32", pairs=train_pos, fp8=False):
            """(the stated lanes' steps, the twin's step) of the reference
            put in the program's place."""
            model = dict(config["model"])
            if fp8:
                model["fp8_lanes"] = ref.CONTROL_FP8_LANES
            main = ref.train_steps(weights, key, x, graph, pairs, model,
                                   steps=steps, compute=jnp.dtype(compute))
            if twin_cfg is None:
                return main, None
            return main, ref.train_steps(
                weights, key, x, graph, pairs, twin_cfg["model"], steps=1,
                compute=jnp.dtype(compute))

        want, want_twin = follow()

        def against(side):
            main, twin = side
            return main, want, None if twin is None else (twin, want_twin)

        line = {"seed": seed,
                "program": judge("program", *against((got, got_twin))),
                "losses": {"program": got["losses"],
                           "reference": want["losses"]}}
        if i < args.controls:
            half = np.asarray(train_pos)[: len(train_pos) // 2]
            for kind, side in (
                    ("control", follow("bfloat16", fp8=True)),
                    ("control_bf16_only", follow("bfloat16")),
                    ("fault_half_batch", follow(pairs=half))):
                line[kind] = judge(kind, *against(side))
        print(json.dumps(line), flush=True)
    print(json.dumps({
        "correct": {k: f"{sum(v)} of {len(v)}" for k, v in verdicts.items()},
        "summary": {f"{kind}.{k}": {"min": lo, "max": hi}
                    for (kind, k), (lo, hi) in sorted(worst.items())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
