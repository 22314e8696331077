"""Readings the limits of a mesh cell's ``correct`` are set from, taken
on the chips at the cell's own size, many seeds in one process:

    python3 benchmark/calibrate_mesh.py --workload hgcn_magcite_lp.mesh4 --seeds 8 [--controls 2] [--faults 2]

``calibrate.py`` for the driver ``train_mesh``.  The program and its
twin are wired once each (partition, placement, one compile) and given
each seed's weights and step key in a fresh state; the plain reference
follows every seed.  For the first ``--controls`` seeds also the
reference one step of precision down, put in the program's place
(``control``: bfloat16 compute and fp8-stored lanes; the twin in
bfloat16).  For the first ``--faults`` seeds also the cell's two faults,
planted in the program under the driver:

- ``fault_no_exchange``: every shard aggregates its local senders only
  (the weights of its cross-shard edges zeroed at partition time);
- ``fault_shard_pairs``: the second data shard's pairs left out of the
  gradient (the loss keeps them); read at the twin's lanes only.

Every one goes through ``correctness.compare_training`` under the
committed limits; one JSON line a seed, the last line sums up, and all
of it also under ``chiprun_out/``.  The benchmark's own runs never call
this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--first-seed", type=int, default=2_000_000_011)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--faults", type=int, default=2)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)

    import jax
    import jax.numpy as jnp

    from benchmark import correctness, guards, mesh_faults
    from benchmark import run as bench_run
    from benchmark.drivers import train_mesh as drv
    from benchmark.reference import hgcn as plain
    from benchmark.reference import hgcn_magcite as ref

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
    data_root = drv.one.ensure_dataset(
        config, os.path.join(ROOT, ".cache", "benchmark"))
    steps = int(traffic["check_steps"])
    twin_cfg = drv.one.twin_config(config)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    made = {s: drv.one.make_weights(s, config["model"]) for s in seeds}

    def program(cfg, n_steps, fault=None, over=None):
        """{seed: readings} of one wiring of the program over ``over``
        (default every seed): wired and compiled once."""
        out = {}
        # the fault stays planted while the program is wired and traced
        with fault() if fault else contextlib.nullcontext():
            built, _, _, _ = drv.seeded_build(cfg, traffic, seeds[0],
                                              data_root, made[seeds[0]])
            shardings = jax.tree_util.tree_map(lambda a: a.sharding,
                                               built.state)
            blank = jax.device_get(built.state)
            for seed in over or seeds:
                weights, key = made[seed]
                tree = drv.one.to_program_tree(weights, blank.params)
                built.state = jax.device_put(
                    blank._replace(params=tree, key=jnp.array(key)),
                    shardings)
                _, out[seed] = drv.one.first_steps(
                    built, drv.one.to_program_tree(weights, blank.params),
                    n_steps)
        kept = built.order, built.split.train_pos, built.pairs
        del built
        drv.unload_programs()
        return (out, *kept)

    got, order, train_pos, pairs = program(config, steps)
    got_twin, _, _, _ = program(twin_cfg, 1)
    x, graph = drv.one.reference_inputs(config, data_root, order, train_pos)

    def follow(seed, compute="float32", fp8=False):
        model = dict(config["model"])
        if fp8:
            model["fp8_lanes"] = plain.CONTROL_FP8_LANES
        weights, key = made[seed]
        kw = dict(compute=jnp.dtype(compute))
        return (ref.train_steps(weights, key, x, graph, pairs, model,
                                steps=steps, **kw),
                ref.train_steps(weights, key, x, graph, pairs,
                                twin_cfg["model"], steps=1, **kw))

    worst: dict = {}
    verdicts: dict = {}
    lines: dict = {s: {"seed": s} for s in seeds}

    def judge(kind, seed, main, twin, want, want_twin):
        checks = correctness.compare_training(main, want, limits,
                                              (twin, want_twin))
        ok = correctness.all_within(checks)
        for name, value, _ in checks:
            lo, hi = worst.get((kind, name), (float("inf"), 0.0))
            worst[(kind, name)] = (min(lo, value), max(hi, value))
        verdicts.setdefault(kind, []).append(ok)
        lines[seed][kind] = {
            "correct": ok,
            "failed": [n for n, v, lim in checks if not v <= lim],
            "gaps": {n: v for n, v, _ in checks}}

    wants = {}
    for i, seed in enumerate(seeds):
        wants[seed] = follow(seed)
        judge("program", seed, got[seed], got_twin[seed], *wants[seed])
        lines[seed]["losses"] = {"program": got[seed]["losses"],
                                 "reference": wants[seed][0]["losses"]}
        if i < args.controls:
            judge("control", seed, *follow(seed, "bfloat16", fp8=True),
                  *wants[seed])
        print(json.dumps(lines[seed]), flush=True)

    faulty = seeds[:args.faults]
    if faulty:
        drv.unload_programs()  # the reference's, before the program's again
        # the first fault changes arrays and no program (no compile); the
        # second is read at the twin's lanes alone, beside the sound
        # program's stated-lane readings: its loss is whole, and one
        # more compile of the step is a minute on four chips
        for kind, fault, stated in (
                ("fault_no_exchange", mesh_faults.exchange_left_out, True),
                ("fault_shard_pairs",
                 mesh_faults.a_data_shards_pairs_left_out, False)):
            f_main = (program(config, steps, fault, faulty)[0] if stated
                      else got)
            f_twin, _, _, _ = program(twin_cfg, 1, fault, faulty)
            for seed in faulty:
                judge(kind, seed, f_main[seed], f_twin[seed], *wants[seed])
                print(json.dumps({"seed": seed, kind: lines[seed][kind]}),
                      flush=True)
    summary = {
        "correct": {k: f"{sum(v)} of {len(v)}" for k, v in verdicts.items()},
        "summary": {f"{kind}.{k}": {"min": lo, "max": hi}
                    for (kind, k), (lo, hi) in sorted(worst.items())}}
    print(json.dumps(summary))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"calibrate_{cell['name']}.json"),
              "w") as f:
        json.dump({"seeds": list(lines.values()), **summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
