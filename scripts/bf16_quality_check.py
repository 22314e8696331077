"""bf16-vs-f32 ROC-AUC parity for HGCN LP at arxiv density.

The north-star metric couples throughput to matching test ROC-AUC
(SURVEY.md §6); bf16 is ~11% faster per step, so this measures what it
costs in quality.  Trains the same split with each dtype config over
several seeds and prints one JSON line per run.

Defaults run the quality phase at the FULL 169 k-node bench scale over 3
seeds (VERDICT r1 #4c: the bench default's quality-neutrality must be
measured at the scale it is reported at, not extrapolated from 32 k):

    python scripts/bf16_quality_check.py                   # full scale, TPU
    python scripts/bf16_quality_check.py --quality-nodes 32768 --seeds 1
"""

from __future__ import annotations

import argparse
import json


def configs(hgcn, jnp, feat_dim, which="all"):
    """(name, cfg) pairs, all stepped by ``train_step_lp``."""
    base = dict(feat_dim=feat_dim, hidden_dims=(128, 32), kind="lorentz")
    all_ = [
        ("f32", hgcn.HGCNConfig(**base)),
        ("f32_aggbf16", hgcn.HGCNConfig(**base, agg_dtype=jnp.bfloat16)),
        ("bf16", hgcn.HGCNConfig(**base, dtype=jnp.bfloat16)),
        # configs/hgcn_arxiv_lp.yaml's lanes: f32 encoder, bf16
        # messages, bf16 decoder pass
        ("f32_aggbf16_decbf16",
         hgcn.HGCNConfig(**base, agg_dtype=jnp.bfloat16,
                         decoder_dtype=jnp.bfloat16)),
    ]
    if which == "all":
        return all_
    names = {t[0] for t in all_}
    sel = which.split(",")
    unknown = [s for s in sel if s not in names]
    if unknown:  # fail fast — a typo must not silently run nothing
        raise SystemExit(
            f"unknown config(s) {unknown}; known: {sorted(names)}")
    return [t for t in all_ if t[0] in sel]


def make_split(num_nodes):
    from hyperspace_tpu.benchmarks import hgcn_bench as HB

    return HB.arxiv_scale_split(num_nodes)


def time_phase(which: str = "all"):
    """Step time per config at full arxiv scale."""
    import time

    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.benchmarks import hgcn_bench as HB
    from hyperspace_tpu.models import hgcn

    split, x = make_split(HB.ARXIV_NODES)
    n = HB.ARXIV_NODES
    ga = hgcn._device_graph(split.graph)
    step = _stepper(hgcn, split, n)
    for name, cfg in configs(hgcn, jnp, x.shape[1], which):
        model, opt, state = hgcn.init_lp(cfg, split.graph, seed=0)
        state, loss = step(model, opt, state, ga)
        jax.device_get(loss)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(10):
                state, loss = step(model, opt, state, ga)
            jax.device_get(loss)
            best = min(best, time.perf_counter() - t0)
        print(json.dumps({"phase": "time", "config": name,
                          "step_s": round(best / 10, 5),
                          "samples_per_s": round(n / (best / 10), 1)}),
              flush=True)


def _stepper(hgcn, split, n):
    """step(model, opt, state, ga) over the split's training positives."""
    import jax.numpy as jnp

    train_pos = jnp.asarray(split.train_pos)
    return lambda m, o, st, g: hgcn.train_step_lp(m, o, n, st, g, train_pos)


def quality_phase(quality_nodes: int, steps: int, seeds: int,
                  which: str = "all"):
    """Converged test ROC-AUC per config per seed at the requested scale."""
    import jax.numpy as jnp

    from hyperspace_tpu.models import hgcn

    split, x = make_split(quality_nodes)
    n = quality_nodes
    ga = hgcn._device_graph(split.graph)
    step = _stepper(hgcn, split, n)
    for name, cfg in configs(hgcn, jnp, x.shape[1], which):
        for seed in range(seeds):
            model, opt, state = hgcn.init_lp(cfg, split.graph, seed=seed)
            for _ in range(steps):
                state, loss = step(model, opt, state, ga)
            res = hgcn.evaluate_lp(model, state.params, split, "test", ga=ga)
            print(json.dumps({"phase": "quality", "config": name,
                              "seed": seed, "nodes": n, "steps": steps,
                              "loss": float(loss), **res}), flush=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quality-nodes", type=int, default=None,
                    help="default: the full bench scale (ARXIV_NODES)")
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--skip-timing", action="store_true")
    ap.add_argument("--configs", default="all",
                    help='comma-separated config names, or "all"')
    args = ap.parse_args()
    if args.quality_nodes is None:
        from hyperspace_tpu.benchmarks import hgcn_bench as HB

        args.quality_nodes = HB.ARXIV_NODES
    if not args.skip_timing:
        time_phase(args.configs)
    quality_phase(args.quality_nodes, args.steps, args.seeds, args.configs)
