"""Rows the MoE LM cell (``laguna_s21.pretrain4k``) routes to its held
experts, layer by layer and step by step, over several seeds: the
benchmark's own wiring and weights (``benchmark/drivers/train_moe_lm.py``),
the program alone (no twin, no reference), one process:

    python3 scripts/moe_held_rows.py --seeds 6 --steps 40 \
        --out held_rows.jsonl

One JSON line a seed: its loss and each sparse layer's rows and held
experts with a row at every step; each step's rows also go to stderr as
they are read.  ``--tiny`` runs the configuration's
``tiny`` size (on the CPU: ``JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "laguna_s21.pretrain4k"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="scripts/moe_held_rows.py")
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=2000000011)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    from benchmark.drivers import train_fullgraph as one
    from benchmark.drivers import train_moe_lm as drv
    from hyperspace_tpu import compile_cache
    from hyperspace_tpu.models import moe_lm

    def load(*parts):
        with open(os.path.join(ROOT, *parts)) as f:
            return json.load(f)

    manifest = load("BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load(entry["file"])
    traffic = load("benchmark", "traffic", cell["traffic"] + ".json")
    if args.tiny:
        from tests.benchmark.helpers import tiny_config

        config = tiny_config(config)
    compile_cache.activate(None)
    data_root = os.path.dirname(one.ensure_dataset(
        config, os.path.join(ROOT, ".cache", "benchmark")))
    out = open(args.out, "a") if args.out else None
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        built, _ = drv.seeded_build(config, traffic, seed, data_root)
        state, rec = built.state, {"seed": seed, "loss": [], "rows": [],
                                   "experts": []}
        for _ in range(args.steps):
            state, loss = built.stepper(state)
            stats = moe_lm.read_stats(built.cfg, state.stats)
            rec["loss"].append(round(float(loss), 4))
            rec["rows"].append(stats["held_rows"])
            rec["experts"].append(stats["held_experts"])
            print(json.dumps({"seed": seed, "step": len(rec["loss"]),
                              "rows": stats["held_rows"]}),
                  file=sys.stderr, flush=True)
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        del built, state
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
