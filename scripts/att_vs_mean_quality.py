"""Does mean aggregation match attention aggregation's quality? (VERDICT r1 #5)

The bench reports the mean-aggregation HGCN (797 k samples/s/chip); the
attention path — closest to Chami et al.'s config — runs at ~321 k.  The
honest options are (a) bench attention, or (b) show mean-agg reaches the
same converged quality on the eval fixtures.  This script measures (b):
same split, use_att False vs True, several seeds, converged test ROC-AUC
on hierarchy graphs (LP) plus NC accuracy.

    JAX_PLATFORMS=cpu \
        python scripts/att_vs_mean_quality.py --nodes 4096 --steps 400
"""

from __future__ import annotations

import argparse
import json


def run_lp(use_att: bool, nodes: int, steps: int, seed: int):
    from hyperspace_tpu.data import graphs as G
    from hyperspace_tpu.models import hgcn

    edges, x, labels, ncls = G.synthetic_hierarchy(
        num_nodes=nodes, feat_dim=16, ancestor_hops=4, seed=seed)
    split = G.split_edges(edges, nodes, x, seed=seed)
    cfg = hgcn.HGCNConfig(feat_dim=x.shape[1], hidden_dims=(64, 16),
                          kind="lorentz", use_att=use_att)
    model, params, _ = hgcn.train_lp(cfg, split, steps=steps, seed=seed)
    ev = hgcn.evaluate_lp(model, params, split, "test")
    return {"task": "lp", "use_att": use_att, "seed": seed,
            "test_roc_auc": round(ev["roc_auc"], 4)}


def run_nc(use_att: bool, nodes: int, steps: int, seed: int):
    from hyperspace_tpu.data import graphs as G
    from hyperspace_tpu.models import hgcn

    edges, x, labels, ncls = G.synthetic_hierarchy(
        num_nodes=nodes, feat_dim=16, ancestor_hops=4, seed=seed)
    tr, va, te = G.node_split_masks(nodes, seed=seed)
    g = G.prepare(edges, nodes, x, labels=labels, num_classes=ncls,
                  train_mask=tr, val_mask=va, test_mask=te)
    cfg = hgcn.HGCNConfig(feat_dim=x.shape[1], hidden_dims=(64, 16),
                          kind="lorentz", use_att=use_att,
                          num_classes=ncls)
    model, params, res = hgcn.train_nc(cfg, g, steps=steps, seed=seed)
    return {"task": "nc", "use_att": use_att, "seed": seed,
            "test_acc": round(res["test_acc"], 4)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--seeds", type=int, default=3)
    args = ap.parse_args()

    import numpy as np

    acc = {("lp", False): [], ("lp", True): [], ("nc", False): [],
           ("nc", True): []}
    for seed in range(args.seeds):
        for use_att in (False, True):
            r = run_lp(use_att, args.nodes, args.steps, seed)
            acc[("lp", use_att)].append(r["test_roc_auc"])
            print(json.dumps(r), flush=True)
            r = run_nc(use_att, args.nodes, args.steps, seed)
            acc[("nc", use_att)].append(r["test_acc"])
            print(json.dumps(r), flush=True)
    summary = {f"{t}_{'att' if a else 'mean'}":
               round(float(np.mean(v)), 4) for (t, a), v in acc.items()}
    print(json.dumps({"summary": summary}), flush=True)


if __name__ == "__main__":
    main()
