"""The readings an MoE LM cell's limits are set from
(``benchmark/limits/<cell>.json``; ``--workload``, default
``laguna_s21.pretrain4k``, or ``glm47_flash.pretrain4k``), one process on
the chip:

    python3 scripts/calibrate_moe_lm.py --seeds 3 --faults 1 \
        --out calibrate_moe.jsonl

For each seed: the program's first steps and its twin's first step
against the plain reference (the sound readings, ``program``); for the
first ``--faults`` seeds also the control (the reference one step of
precision down) and each planted fault of the cell's reference
(``benchmark/reference/laguna.py``, ``glm47_flash.py``: the driver's
``ref``), put in the program's place and in its twin's.  ``--kinds`` names which of these to read, in that order
(``--kinds control,no_window`` leaves the program out: the harness's own
runs give its readings).  One JSON line a seed and variant, written as
each is read.  ``--tiny`` runs the configuration's
``tiny`` size (on the CPU: ``JAX_PLATFORMS=cpu``).  ``--rejudge FILE``
gives recorded readings their verdict under the committed limits.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "laguna_s21.pretrain4k"


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _limits(cell):
    return _load("benchmark", "limits", cell + ".json")["limits"]


def judge(gaps: dict, limits: dict) -> list:
    """The limits a reading fails (a NaN fails every limit)."""
    return sorted(n for n, lim in limits.items()
                  if n in gaps and not gaps[n] <= lim)


def _batches(config, steps: int, data_root):
    """The first ``steps`` batches of the cell's token stream, as the
    program reads them."""
    from benchmark.drivers import train_lm as lm
    from hyperspace_tpu.cli import train as T
    from hyperspace_tpu.data import text

    run, overrides = T.split_overrides(lm.program_pairs(config),
                                       T.RunConfig())
    cfg, stream_kw = T._moe_lm_config(run, overrides)
    tokens, _ = text.load_token_stream(data_root, vocab_size=cfg.vocab_size,
                                       **stream_kw)
    return lm.host_batches(tokens, steps, cfg.sequence_length,
                           cfg.sequences_per_step)


def one_seed(drv, config, traffic, seed, data_root, kinds):
    import jax.numpy as jnp
    import numpy as np

    b1 = float(config["recipe"]["adam_b1"])
    steps = int(traffic["check_steps"])
    t0 = time.perf_counter()
    batches = _batches(config, steps, data_root)
    if "program" in kinds:
        built, start = drv.seeded_build(config, traffic, seed, data_root)
        _, got = drv.first_steps(built, start, steps, b1)
        # this frame lives as long as the generator: free the program's
        # state before the twin's is made
        del built, start, _
        twin = drv.first_step_of_twin(config, traffic, seed, data_root)
    want = drv.follow_reference(config, batches, seed, blocked=True)
    if "program" in kinds:
        yield "program", {**drv.gaps(got, want), **drv.twin_gaps(twin, want),
                          "held_rows": got["held_rows"],
                          "seconds": time.perf_counter() - t0}
        del got, twin
    for kind in (k for k in kinds if k != "program"):
        t0 = time.perf_counter()
        kw = {"low": jnp.bfloat16} if kind == "control" else {"fault": kind}
        other = drv.follow_reference(config, batches, seed, blocked=True,
                                     **kw)
        # the readings a program would hand in: its own clipped gradient
        other["grads"] = {k: np.asarray(v) * np.float32(
            min(1.0, other["clip_norm"] / other["grad_norm"]))
            for k, v in other["grads"].items()}
        yield kind, {**drv.gaps(other, want), **drv.twin_gaps(other, want),
                     "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="scripts/calibrate_moe_lm.py")
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2000000011)
    ap.add_argument("--faults", type=int, default=1,
                    help="seeds, from the first, that also read each "
                         "planted fault and the control")
    ap.add_argument("--kinds", default="",
                    help="comma-separated variants to read of 'program', "
                         "'control' and the faults; default all")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--rejudge", default=None)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    limits = _limits(args.workload)
    if args.rejudge:
        tally = {}
        with open(args.rejudge) as f:
            for line in f:
                rec = json.loads(line)
                failed = judge(rec["gaps"], limits)
                ok = tally.setdefault(rec["kind"], [0, 0])
                ok[0] += not failed
                ok[1] += 1
                print(json.dumps({"seed": rec["seed"], "kind": rec["kind"],
                                  "failed": failed}))
        print(json.dumps({"correct": {k: f"{a} of {n}" for k, (a, n)
                                      in tally.items()}}))
        return 0

    import importlib

    from benchmark.drivers import train_fullgraph as one
    from hyperspace_tpu import compile_cache

    manifest = _load("BENCHMARK.json")
    cell = next(w for w in manifest["workloads"]
                if w["name"] == args.workload)
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = _load(entry["file"])
    traffic = _load("benchmark", "traffic", cell["traffic"] + ".json")
    drv = importlib.import_module("benchmark.drivers." + traffic["driver"])
    if args.tiny:
        from tests.benchmark.helpers import tiny_config

        config = tiny_config(config)
    compile_cache.activate(None)
    data_root = os.path.dirname(one.ensure_dataset(
        config, os.path.join(ROOT, ".cache", "benchmark")))
    kinds = ([k for k in args.kinds.split(",") if k] if args.kinds
             else ["program", "control", *drv.ref.FAULTS])
    out = open(args.out, "a") if args.out else None
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        for kind, gaps in one_seed(drv, config, traffic, seed, data_root,
                                   kinds if i < args.faults else
                                   [k for k in kinds if k == "program"]):
            rec = {"seed": seed, "kind": kind, "gaps": gaps,
                   "failed": judge(gaps, limits)}
            line = json.dumps(rec)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
