"""Compare HGCN LP train-step variants on the live backend (TPU or CPU).

Variants:
  unplanned  — train_step_lp: fresh (u, v) negatives, XLA scatter decoder grads
  planned    — train_step_lp_planned: graph-edge positives + corrupt-one-side
               negatives, every decoder gradient scatter CSR-planned
  pairs      — train_step_lp_pairs: exactly the train positives with BOTH
               decoder scatters planned + corrupt-v negatives (u planned);
               same pair count as unplanned, same scatter story as planned
  bf16       — each variant re-run in bfloat16 / with bf16 edge messages

Prints one JSON line per variant.
"""

from __future__ import annotations

import json
import time


def timed(step, state, *args, steps=10, repeats=3):
    import jax

    state, loss = step(state, *args)  # compile + warmup
    jax.device_get(loss)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss = step(state, *args)
        jax.device_get(loss)
        best = min(best, time.perf_counter() - t0)
    return best / steps, state


def main():
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.benchmarks import hgcn_bench as HB
    from hyperspace_tpu.models import hgcn

    num_nodes = HB.ARXIV_NODES
    split, x = HB.arxiv_scale_split(num_nodes)

    # one-time host-side prep shared by every combo
    n_neg_edges = int(split.graph.senders.shape[0])
    neg_u, neg_plan = hgcn.make_static_negatives(num_nodes, n_neg_edges, seed=0)
    pos = hgcn.make_planned_pairs(split.train_pos, num_nodes)
    neg_u3, neg_plan3 = hgcn.make_static_negatives(
        num_nodes, int(pos.u.shape[0]), seed=0)

    combos = (
        ("f32", jnp.float32, None, None),
        ("f32_aggbf16", jnp.float32, jnp.bfloat16, None),
        # bench default (pairs row): + bf16 decoder pass
        ("f32_aggbf16_decbf16", jnp.float32, jnp.bfloat16, jnp.bfloat16),
        ("bf16", jnp.bfloat16, None, None),
    )
    for name, dtype, agg_dtype, decoder_dtype in combos:
        cfg = hgcn.HGCNConfig(feat_dim=x.shape[1], hidden_dims=(128, 32),
                              kind="lorentz", dtype=dtype,
                              agg_dtype=agg_dtype,
                              decoder_dtype=decoder_dtype)
        model, opt, state = hgcn.init_lp(cfg, split.graph, seed=0)
        ga = hgcn._device_graph(split.graph)

        # unplanned
        train_pos = jnp.asarray(split.train_pos)
        t, _ = timed(
            lambda st, g, tp: hgcn.train_step_lp(model, opt, num_nodes, st, g, tp),
            state, ga, train_pos)
        print(json.dumps({"variant": f"unplanned_{name}",
                          "step_s": round(t, 5),
                          "samples_per_s": round(num_nodes / t, 1)}), flush=True)

        # planned
        model2, opt2, state2 = hgcn.init_lp(cfg, split.graph, seed=0)
        t, _ = timed(
            lambda st, g, nu, npl: hgcn.train_step_lp_planned(
                model2, opt2, num_nodes, st, g, nu, npl),
            state2, ga, neg_u, neg_plan)
        print(json.dumps({"variant": f"planned_{name}",
                          "step_s": round(t, 5),
                          "samples_per_s": round(num_nodes / t, 1)}), flush=True)

        # pairs (fully-planned decoder on the actual train positives)
        model3, opt3, state3 = hgcn.init_lp(cfg, split.graph, seed=0)
        t, _ = timed(
            lambda st, g, p, nu, npl: hgcn.train_step_lp_pairs(
                model3, opt3, num_nodes, st, g, p, nu, npl),
            state3, ga, pos, neg_u3, neg_plan3)
        print(json.dumps({"variant": f"pairs_{name}",
                          "step_s": round(t, 5),
                          "samples_per_s": round(num_nodes / t, 1)}), flush=True)


if __name__ == "__main__":
    main()
