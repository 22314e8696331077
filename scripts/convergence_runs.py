"""Full-scale HGCN LP convergence runs (VERDICT r2 next #3).

Trains the bench-scale (169 k-node) graph to AUC plateau for three arms —
the f32 control, the bf16 bench default, and attention aggregation with
the same dtype policy — 3 seeds each, logging a val-AUC curve every
``--eval-every`` steps and the final test AUC.  One JSON line per event;
tee stdout into docs/data/ and summarize in docs/benchmarks.md.

Seed-major order: after one seed's worth of wall-clock every arm has a
complete curve, so a truncated session still yields a comparable table.

    python scripts/convergence_runs.py --steps 6000 --eval-every 500
"""

from __future__ import annotations

import argparse
import json
import time


def arms(hgcn, jnp, feat_dim, which="all"):
    base = dict(feat_dim=feat_dim, hidden_dims=(128, 32), kind="lorentz")
    all_ = [
        # f32 control through the same planned-pairs step as the bench
        ("pairs_f32", hgcn.HGCNConfig(**base)),
        # the bench default: f32 compute, bf16 edge messages + decoder pass
        ("pairs_f32_aggbf16_decbf16",
         hgcn.HGCNConfig(**base, agg_dtype=jnp.bfloat16,
                         decoder_dtype=jnp.bfloat16)),
        # attention aggregation under the identical dtype policy — the
        # mean-vs-att quality comparison at bench scale, 3 seeds
        ("pairs_att_aggbf16_decbf16",
         hgcn.HGCNConfig(**base, use_att=True, agg_dtype=jnp.bfloat16,
                         decoder_dtype=jnp.bfloat16)),
        # stabilized attention arms (seed-0 att at lr=1e-2 trained to
        # val-AUC 0.596 by step 500 then diverged to chance by 1000):
        # lower lr with the bench dtype policy, and an f32-message control
        # to separate the lr effect from bf16-gradient noise
        ("pairs_att_lr3e3_aggbf16_decbf16",
         hgcn.HGCNConfig(**{**base, "lr": 3e-3}, use_att=True,
                         agg_dtype=jnp.bfloat16, decoder_dtype=jnp.bfloat16)),
        ("pairs_att_lr3e3_f32",
         hgcn.HGCNConfig(**{**base, "lr": 3e-3}, use_att=True)),
        # r04 shipped attention defaults: lr 3e-3 + grad clip 1.0 (what
        # `use_att=true` now builds via cli.train.hgcn_mode_defaults),
        # on the bounded-logit softmax + fused planned aggregation path
        ("pairs_att_stab",
         hgcn.HGCNConfig(**{**base, "lr": 3e-3, "clip_norm": 1.0},
                         use_att=True, agg_dtype=jnp.bfloat16,
                         decoder_dtype=jnp.bfloat16)),
    ]
    if which == "all":
        return all_
    sel = which.split(",")
    unknown = [s for s in sel if s not in {n for n, _ in all_}]
    if unknown:
        raise SystemExit(f"unknown arm(s) {unknown}")
    return [t for t in all_ if t[0] in sel]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=None,
                    help="default: full bench scale (ARXIV_NODES)")
    ap.add_argument("--steps", type=int, default=6000)
    ap.add_argument("--eval-every", type=int, default=500)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--seed-start", type=int, default=0,
                    help="resume a truncated session at this seed")
    ap.add_argument("--arms", default="all")
    ap.add_argument("--dataset", choices=["synthetic", "realistic"],
                    default="synthetic",
                    help="realistic = the DC-SBM disk dataset through the "
                         "full disk -> loader -> community-reorder -> "
                         "split path (VERDICT r4 #3); hub-skewed degree "
                         "distribution, ~30%% clusterable")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.benchmarks import hgcn_bench as HB
    from hyperspace_tpu.models import hgcn

    if args.dataset == "realistic":
        from hyperspace_tpu.data import graphs as G

        if args.nodes is not None:
            raise SystemExit(
                "--nodes only applies to the synthetic dataset; the "
                "realistic disk graph has a fixed node count")
        root = G.ensure_arxiv_scale_dataset()
        edges, x, labels, ncls, source = G.load_graph("ogbn-arxiv", root)
        edges, x, labels, _ = G.apply_locality_order(edges, x, labels,
                                                     method="community")
        n = x.shape[0]
        split = G.split_edges(edges, n, x, val_frac=0.02, test_frac=0.02,
                              seed=0, pad_multiple=65536)
        print(json.dumps({
            "phase": "dataset", "dataset": "realistic", "source": source,
            "num_nodes": n,
            "frac_clustered": (
                None if split.graph.cluster_split is None else
                round(split.graph.cluster_split.frac_clustered, 4)),
        }), flush=True)
    else:
        n = args.nodes or HB.ARXIV_NODES
        split, x = HB.arxiv_scale_split(n)
    ga = hgcn._device_graph(split.graph)
    pos = hgcn.make_planned_pairs(split.train_pos, n)
    neg_u, neg_plan = hgcn.make_static_negatives(n, int(pos.u.shape[0]), seed=0)
    sel = arms(hgcn, jnp, x.shape[1], args.arms)

    for seed in range(args.seed_start, args.seeds):
        for name, cfg in sel:
            model, opt, state = hgcn.init_lp(cfg, split.graph, seed=seed)
            t0 = time.perf_counter()
            for i in range(args.steps):
                state, loss = hgcn.train_step_lp_pairs(
                    model, opt, n, state, ga, pos, neg_u, neg_plan)
                if (i + 1) % args.eval_every == 0:
                    ev = hgcn.evaluate_lp(model, state.params, split, "val",
                                          ga=ga)
                    print(json.dumps({
                        "phase": "curve", "config": name, "seed": seed,
                        "step": i + 1, "loss": float(loss),
                        "val_auc": round(ev["roc_auc"], 4),
                        "elapsed_s": round(time.perf_counter() - t0, 1),
                    }), flush=True)
            test = hgcn.evaluate_lp(model, state.params, split, "test", ga=ga)
            val = hgcn.evaluate_lp(model, state.params, split, "val", ga=ga)
            print(json.dumps({
                "phase": "final", "config": name, "seed": seed,
                "nodes": n, "steps": args.steps, "loss": float(loss),
                "test_auc": round(test["roc_auc"], 4),
                "val_auc": round(val["roc_auc"], 4),
                "train_s": round(time.perf_counter() - t0, 1),
            }), flush=True)


if __name__ == "__main__":
    main()
