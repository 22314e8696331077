"""Hyperbolic-vs-Euclidean quality control for HGCN (VERDICT r1 #4a).

Trains the *same* architecture (HGCConv stack + Fermi–Dirac LP decoder,
one shared codepath) with kind="lorentz" vs kind="euclidean" (flat GCN
control) on hierarchy graphs, several seeds each, and prints one JSON
line per run plus a summary.  The point: on hierarchical data the
hyperbolic model must beat the flat control, anchoring the "matching
ROC-AUC" claim to a falsifiable comparison while the real reference
datasets are unavailable.

    JAX_PLATFORMS=cpu \
        python scripts/euclidean_control.py --nodes 4096 --steps 400
"""

from __future__ import annotations

import argparse
import json


def run_one(kind: str, nodes: int, steps: int, seed: int,
            feat_dim: int = 16, ancestor_hops: int = 4):
    import jax.numpy as jnp

    from hyperspace_tpu.data import graphs as G
    from hyperspace_tpu.models import hgcn

    edges, x, labels, ncls = G.synthetic_hierarchy(
        num_nodes=nodes, feat_dim=feat_dim, ancestor_hops=ancestor_hops,
        seed=seed)
    split = G.split_edges(edges, nodes, x, seed=seed)
    cfg = hgcn.HGCNConfig(feat_dim=x.shape[1], hidden_dims=(64, 16),
                          kind=kind)
    model, opt, state = hgcn.init_lp(cfg, split.graph, seed=seed)
    ga = hgcn._device_graph(split.graph)
    train_pos = jnp.asarray(split.train_pos)
    for _ in range(steps):
        state, loss = hgcn.train_step_lp(model, opt, nodes, state, ga,
                                         train_pos)
    ev = hgcn.evaluate_lp(model, state.params, split, "test", ga=ga)
    return {"kind": kind, "seed": seed, "nodes": nodes, "steps": steps,
            "loss": round(float(loss), 4),
            "test_roc_auc": round(ev["roc_auc"], 4)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--seeds", type=int, default=3)
    args = ap.parse_args()

    import numpy as np

    results = {"lorentz": [], "euclidean": []}
    for seed in range(args.seeds):
        for kind in ("lorentz", "euclidean"):
            r = run_one(kind, args.nodes, args.steps, seed)
            results[kind].append(r["test_roc_auc"])
            print(json.dumps(r), flush=True)
    summary = {
        "lorentz_auc_mean": round(float(np.mean(results["lorentz"])), 4),
        "euclidean_auc_mean": round(float(np.mean(results["euclidean"])), 4),
        "delta": round(float(np.mean(results["lorentz"])
                             - np.mean(results["euclidean"])), 4),
    }
    print(json.dumps({"summary": summary}), flush=True)


if __name__ == "__main__":
    main()
