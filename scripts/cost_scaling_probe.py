"""Per-device compiled-cost scaling of the node-sharded HGCN step.

The BASELINE north star is "HGCN on v5e-16"; real 16-chip hardware is not
available in this environment, so the scaling evidence is compiled-cost
analysis on a virtual CPU mesh (the same probe the r2 verdict used to show
the pair-sharded step did NOT scale).  This script forces ``--ndev``
virtual devices, compiles the node-sharded LP step at each data-parallel
degree in ``--dp-list``, and prints one JSON line with per-device FLOPs
and HBM-bytes ratios relative to the compiled single-device step.

Run standalone::

    python scripts/cost_scaling_probe.py --ndev 16

or via the drill in tests/parallel/test_node_sharded.py (marked slow),
which asserts dp=16 leaves <=20% of single-device FLOPs per device.
"""

from __future__ import annotations

import argparse
import json
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ndev", type=int, default=16)
    ap.add_argument("--num-nodes", type=int, default=2048)
    ap.add_argument("--dp-list", type=str, default="1,4,8,16")
    ap.add_argument("--reorder", choices=["none", "bfs", "community"],
                    default="none",
                    help="locality relabeling before sharding: under "
                         "'community' the halo exchange replaces the "
                         "all-gather wherever its static volume wins")
    args = ap.parse_args()

    # virtual CPU devices must be configured before jax import; an
    # inherited device-count flag (e.g. the test conftest's 8) must be
    # REPLACED, not kept, or dp > 8 has too few devices
    os.environ["JAX_PLATFORMS"] = "cpu"
    kept = [f for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f]
    os.environ["XLA_FLAGS"] = " ".join(
        kept + [f"--xla_force_host_platform_device_count={args.ndev}"])

    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.data import graphs as G
    from hyperspace_tpu.models import hgcn
    from hyperspace_tpu.parallel.mesh import make_mesh

    n = args.num_nodes
    edges, x, _, _ = G.synthetic_hierarchy(num_nodes=n, feat_dim=16, seed=0)
    if args.reorder != "none":
        edges, x, _, _ = G.apply_locality_order(edges, x, None,
                                                method=args.reorder)
    split = G.split_edges(edges, n, x, seed=0, pad_multiple=256)
    cfg = hgcn.HGCNConfig(feat_dim=16, hidden_dims=(32, 8))

    model, opt, state = hgcn.init_lp(cfg, split.graph, seed=0)
    ga = G.to_device(split.graph)
    pairs = jnp.asarray(split.train_pos[:256])
    from hyperspace_tpu.train.profiling import cost_analysis_dict

    single = cost_analysis_dict(jax.jit(
        lambda st, g, p: hgcn._lp_step_impl(model, opt, n, st, g, p)
    ).lower(state, ga, pairs).compile())

    out = {"ndev": args.ndev, "num_nodes": n, "reorder": args.reorder,
           "single_flops": single["flops"],
           "single_bytes": single["bytes accessed"], "dp": {}}
    for dp in (int(d) for d in args.dp_list.split(",")):
        if dp > args.ndev or n % dp:
            continue
        mesh = make_mesh({"data": dp}, devices=jax.devices()[:dp])
        model_k, opt_k, state_k = hgcn.init_lp(cfg, split.graph, seed=0)
        tp = jnp.asarray(hgcn.round_up_pairs(split.train_pos[:256], mesh))
        step, state_k, nsg = hgcn.make_node_sharded_step_lp(
            model_k, opt_k, n, mesh, state_k, split)
        cost = cost_analysis_dict(step.lower(state_k, nsg, tp).compile())
        out["dp"][str(dp)] = {
            "halo": bool(nsg.halo),
            "flops_ratio": round(cost["flops"] / single["flops"], 4),
            "bytes_ratio": round(
                cost["bytes accessed"] / single["bytes accessed"], 4),
        }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
