"""A tiny full-graph HGCN link-prediction step for tests that read the
program's structure (kernel names, scopes, profiler spans): the
generated community graph cut to 1,500 nodes, prepared as ``cli.train
hgcn --yaml configs/hgcn_arxiv_lp.yaml`` prepares it (BFS order, bf16
lanes).  The cluster split, which ``prepare`` builds only from 200,000
edges up, is built here by hand (about half of the edges clustered), so both arms take
the cluster kernels and the straggler path as they do at the arxiv
shape."""

from __future__ import annotations

import functools

import jax.numpy as jnp

GRAPH = {"num_nodes": 1500, "num_edges": 9000, "num_classes": 6,
         "feat_dim": 16, "sub_size": 60}


@functools.lru_cache(maxsize=2)
def lp_step(use_att: bool):
    """(step, state): ``step(state) -> (state, loss)`` is the jitted
    ``hgcn.train_step_lp`` over the tiny graph; ``step.lower(state)``
    and ``jax.make_jaxpr(step)(state)`` see the whole program."""
    from hyperspace_tpu.data import graphs as G
    from hyperspace_tpu.kernels.cluster import build_cluster_split
    from hyperspace_tpu.models import hgcn
    from hyperspace_tpu.precision import parse_dtype

    edges, x, labels, _ = G.community_power_law_graph(seed=0, **GRAPH)
    edges, x, labels, _ = G.apply_locality_order(edges, x, labels,
                                                 method="bfs", cache=False)
    split = G.split_edges(edges, x.shape[0], x, seed=0, cache=False)
    g = split.graph
    g.cluster_split = build_cluster_split(
        g.senders, g.receivers, g.edge_mask, g.deg, g.num_nodes,
        min_pair_edges=512, rev_perm=g.rev_perm)
    assert 0.1 < g.cluster_split.frac_clustered < 1.0
    cfg = hgcn.HGCNConfig(
        feat_dim=GRAPH["feat_dim"], hidden_dims=(16, 8), kind="lorentz",
        use_att=use_att, agg_dtype=parse_dtype("bfloat16"),
        decoder_dtype=parse_dtype("bfloat16"))
    model, opt, state = hgcn.init_lp(cfg, split.graph, seed=0)
    ga = hgcn._device_graph(split.graph)
    train_pos = jnp.asarray(split.train_pos)
    n = split.graph.num_nodes

    def step(st):
        return hgcn.train_step_lp(model, opt, n, st, ga, train_pos)

    step.lower = lambda st: hgcn.train_step_lp.lower(model, opt, n, st, ga,
                                                     train_pos)
    return step, state
