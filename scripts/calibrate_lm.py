"""Readings the limits of ``correct`` are set from, for a language-model
cell (driver ``train_lm``), taken on the chip at the cell's own size,
many seeds in one process:

    python3 scripts/calibrate_lm.py --workload ouro_2p6b.pretrain4k --seeds 10 --first-seed <n> [--controls 3]
    python3 scripts/calibrate_lm.py --workload ouro_2p6b.pretrain4k --rejudge <its output> [...]

For every seed: the program's first steps against the plain reference
(the lower readings).  For the first ``--controls`` seeds also, each put
in the program's place and followed through every step of the check:

- ``control``: the reference one step of precision down (bf16 residual
  stream, norms, softmax and matmuls), in the program's place and in its
  twin's;
- ``three_passes``: three passes run for four;
- ``last_pass_loss``: the last pass's loss alone (the earlier passes'
  gradient paths cut);
- ``uniform_exit``: the exit gate ignored (every pass weighted alike);
- ``half_head_blocks``: the head's cross-entropy run on the first two of
  its four row blocks, the mean taken over what ran;
- ``unchained_passes``: every pass started from the embedding.

Every one of them goes through the driver's own comparison
(``train_lm.gaps`` / ``twin_gaps``) under the committed limits of the
cell, and the line says what ``correct`` came out as; every number the
comparison can read is printed, held by a limit or not.  Beside them,
held by no limit, ``leaves``: the stated lane's gradient leaf by leaf
(median leaf, worst leaf of each kind) for the program, its twin and
the control (``--leaves``: the program's and its twin's on every
seed; the host's float64 norms over every leaf cost ~110 s a seed at
the cell's size).  One JSON line a seed and one for each variant of it, as
it is read; the last line sums up (the smallest and largest reading of
every number by kind).  ``--rejudge`` needs no
chip: it reads such lines again and gives each recorded set of gaps its
verdict under the limits as committed now.  The benchmark's own runs
never call this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=10)
    ap.add_argument("--first-seed", type=int, default=2_000_000_011)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--kinds", nargs="+", metavar="KIND",
                    help="which of control and the faults, in this "
                         "order (default: all)")
    ap.add_argument("--leaves", action="store_true",
                    help="the program's gradient leaf by leaf on every "
                         "seed, not only beside the control")
    ap.add_argument("--tiny", action="store_true",
                    help="the configuration's CPU test size, kernels "
                         "as they come (a rehearsal, not a reading)")
    ap.add_argument("--rejudge", nargs="+", metavar="JSONL",
                    help="no run: the recorded gaps of these outputs "
                         "under the limits as committed now")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    if args.rejudge:
        return rejudge(args.workload, args.rejudge)

    import jax.numpy as jnp
    import numpy as np

    from benchmark import correctness, guards
    from benchmark import run as bench_run
    from benchmark.drivers import train_lm as drv
    from benchmark.reference import looplm as ref

    manifest = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, centry = bench_run.find_cell(manifest, args.workload)
    config = bench_run.load_json(os.path.join(ROOT, centry["file"]))
    if args.tiny:
        from tests.benchmark import helpers

        config = helpers.tiny_config(config)
    else:
        guards.require_chip(int(cell["chips"]))
        guards.require_pallas()
    traffic = bench_run.load_json(os.path.join(
        ROOT, "benchmark", "traffic", cell["traffic"] + ".json"))
    limits = bench_run.load_json(os.path.join(
        ROOT, "benchmark", "limits", cell["name"] + ".json"))["limits"]
    from hyperspace_tpu import compile_cache

    compile_cache.activate(None)
    data_root = os.path.dirname(drv.one.ensure_dataset(
        config, os.path.join(ROOT, ".cache", "benchmark")))
    steps = int(traffic["check_steps"])
    b1 = float(config["recipe"]["adam_b1"])
    worst: dict = {}
    verdicts: dict = {}

    def judge(kind, got, want, got_twin=None):
        found = drv.gaps(got, want)
        if got_twin is not None:
            # a variant stands in both places: its gaps are read once
            found.update(drv.twin_gaps(
                got_twin, want, found if got_twin is got else None))
        checks = [(name, found[name], float(limit))
                  for name, limit in limits.items()]
        ok = correctness.all_within(checks)
        for name, value in found.items():   # held by a limit or not
            lo, hi = worst.get((kind, name), (float("inf"), 0.0))
            worst[(kind, name)] = (min(lo, value), max(hi, value))
        verdicts.setdefault(kind, []).append(ok)
        return {"correct": ok,
                "failed": [n for n, v, lim in checks if not v <= lim],
                "gaps": found}

    def leaves(got, want):
        """The gradient leaf by leaf, as ``grad_difference`` reads its
        worst one: the median leaf, and the worst leaf of each kind."""
        scale = np.float32(min(1.0, want["clip_norm"] / want["grad_norm"]))
        norm = lambda a: float(np.sqrt(np.sum(np.square(a, dtype=np.float64))))
        ref_norms = {k: norm(v) * float(scale)
                     for k, v in want["grads"].items()}
        floor = float(np.median(list(ref_norms.values())))
        rel = {k: norm(np.asarray(got["grads"][k]) - np.asarray(v) * scale)
               / max(ref_norms[k], floor, 1e-30)
               for k, v in want["grads"].items()}
        by_kind: dict = {}
        for k, v in rel.items():
            kind = k.split(".")[-1]
            by_kind[kind] = max(by_kind.get(kind, 0.0), v)
        return {"median": float(np.median(list(rel.values()))),
                "worst": max(rel.values()), "by_kind": by_kind}

    def as_a_program_hands_in(readings):
        """A reference variant in the program's place: its gradient as
        its own clip left it (what the program reads out of Adam)."""
        scale = np.float32(min(1.0, readings["clip_norm"]
                               / readings["grad_norm"]))
        readings["grads"] = {k: np.asarray(v) * scale
                             for k, v in readings["grads"].items()}
        return readings

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        built, start = drv.seeded_build(config, traffic, seed, data_root)
        state, got = drv.first_steps(built, start, steps, b1)
        batches = drv.host_batches(built.tokens, steps,
                                   built.cfg.sequence_length,
                                   built.cfg.sequences_per_step)
        del built, start, state   # the twin's step needs the memory
        gc.collect()
        got_twin = drv.first_step_of_twin(config, traffic, seed, data_root)
        t1 = time.perf_counter()
        follow = lambda batches=batches, **kw: drv.follow_reference(
            config, batches, seed, blocked=not args.tiny, **kw)
        want = follow()
        t2 = time.perf_counter()
        line = {"seed": seed,
                "program": judge("program", got, want, got_twin),
                "losses": {"program": got["losses"],
                           "reference": want["losses"]},
                "step1": {"ce": [got["ce"], want["ce"]],
                          "exit_prob": [got["exit_prob"],
                                        want["exit_prob"]],
                          "grad_norm": [got["grad_norm"],
                                        want["grad_norm"]]},
                "seconds": {"program": t1 - t0, "reference": t2 - t1}}
        kinds = {"control": {"low": jnp.bfloat16},
                 **{fault: {"fault": fault} for fault in ref.FAULTS}}
        kinds = [(k, kinds[k]) for k in (args.kinds or kinds)
                 ] if i < args.controls else []
        if args.leaves or "control" in dict(kinds):
            line["leaves"] = {"program": leaves(got, want)}
            if got_twin is not None:
                line["leaves"]["twin"] = leaves(got_twin, want)
        del got
        print(json.dumps(line), flush=True)
        for kind, kw in kinds:   # a line each, as it is read
            t3 = time.perf_counter()
            # through every step, and in the twin's place too
            other = as_a_program_hands_in(follow(**kw))
            line = {"seed": seed,
                    kind: judge(kind, other, want,
                                None if got_twin is None else other)}
            if kind == "control":
                line["leaves"] = {"control": leaves(other, want)}
            del other
            line["seconds"] = {kind: time.perf_counter() - t3}
            print(json.dumps(line), flush=True)
        del want, got_twin
        gc.collect()
    print(json.dumps({
        "correct": {k: f"{sum(v)} of {len(v)}" for k, v in verdicts.items()},
        "summary": {f"{kind}.{k}": {"min": lo, "max": hi}
                    for (kind, k), (lo, hi) in sorted(worst.items())}}))
    return 0


def rejudge(workload: str, paths: list) -> int:
    """Every recorded set of gaps under the limits as committed now."""
    from benchmark import correctness
    from benchmark import run as bench_run

    limits = bench_run.load_json(os.path.join(
        ROOT, "benchmark", "limits", workload + ".json"))["limits"]
    verdicts: dict = {}
    for path in paths:
        with open(path) as f:
            lines = [json.loads(x) for x in f if x.startswith("{")]
        for line in lines:
            for kind, rec in line.items():
                if not (isinstance(rec, dict) and "gaps" in rec):
                    continue
                # (an output from before a number was recorded, as a
                # variant followed for one step, is not judged on it)
                checks = [(n, rec["gaps"][n], float(lim))
                          for n, lim in limits.items() if n in rec["gaps"]]
                failed = [n for n, v, lim in checks if not v <= lim]
                verdicts.setdefault(kind, []).append(
                    correctness.all_within(checks))
                print(json.dumps({"seed": line["seed"], "kind": kind,
                                  "correct": not failed, "failed": failed}))
    print(json.dumps({"correct": {k: f"{sum(v)} of {len(v)}"
                                  for k, v in verdicts.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
