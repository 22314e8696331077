"""Driver ``train_mesh``: full-graph HGCN link-prediction training on
the mesh ``cli.train`` picks for the devices it finds, node-sharded,
stepped by ``hyperspace_tpu.train.loop.run_loop``.

:func:`build` repeats the mesh branch of ``cli.train.run_hgcn`` call for
call (``auto_mesh`` -> ``round_up_pairs`` -> ``distribute_batch`` ->
``make_node_sharded_step_lp``; a test pins the two to the same
first-step loss on four virtual devices).  Everything that does not
depend on the wiring is ``train_fullgraph``'s, used as it is: the
weights from ``--seed``, the readings of the first steps, the loop, the
traced loop, the memory reading.  :func:`run` follows that driver's
order (its docstring); what differs:

- the window's rate is per chip: nodes x steps / seconds / chips;
- ``memory_peak_bytes`` is the fullest device's;
- the plain reference is the blocked one (``reference/hgcn_magcite``:
  one device, the whole graph), handed the batch of positives as the
  configuration states it (the training pairs repeated up to a multiple
  of the mesh's data extent; :func:`check_batch` holds the program's
  batch to that before a step runs);
- ``facts`` carry the partition's gauges (``node_shard/*``) for the
  exchange's metrics.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import os
import time

import numpy as np

from benchmark import correctness, guards
from benchmark.drivers import train_fullgraph as one
from benchmark.guards import Refused
from benchmark.reference import hgcn_magcite as ref

GAUGES = "node_shard/"


@dataclasses.dataclass
class Built:
    run: object          # cli.train.RunConfig
    state: object
    stepper: object
    steps_per_call: int
    split: object        # data.graphs.LinkSplit (host)
    order: np.ndarray    # node relabeling, order[rank] = raw id
    data: dict
    marks: dict          # seconds each part of the wiring took
    pairs: np.ndarray    # the step's positives as the mesh takes them
    mesh: object


def build(run, overrides: dict, split_seed: int, inject=None) -> Built:
    """The mesh LP branch of ``cli.train.run_hgcn``, call for call, up
    to the step loop.  ``inject(state) -> state`` runs between
    ``init_lp`` and the step builder (which places the state)."""
    import jax.numpy as jnp

    from hyperspace_tpu.cli import train as T
    from hyperspace_tpu.data import graphs as G
    from hyperspace_tpu.models import hgcn
    from hyperspace_tpu.parallel import multihost as mh
    from hyperspace_tpu.parallel.mesh import auto_mesh

    marks, t_mark = {}, [time.perf_counter()]

    def mark(name):
        now = time.perf_counter()
        marks[name], t_mark[0] = now - t_mark[0], now

    overrides = dict(overrides)
    T._reject_accum(run, "hgcn")
    if run.scan_chunk > 1:
        run = T._chunk_run(run)
    gc_ = T._graph_cache(run)
    task = overrides.pop("task", "lp")
    dataset = overrides.pop("dataset", "cora")
    reorder = overrides.pop("reorder", "false").lower()
    if task != "lp" or overrides.pop("sampled", "false").lower() in (
            "1", "true", "yes"):
        raise Refused("train_mesh drives the full-graph LP step only")
    edges, x, labels, ncls, source = one._load_graph(dataset, run.data_root)
    mark("setup_load_graph_s")
    data = {"dataset": dataset, "source": source,
            "num_nodes": int(x.shape[0]), "num_edges": int(len(edges)),
            "feat_dim": int(x.shape[1])}
    order = np.arange(x.shape[0])
    if reorder in ("1", "true", "yes", "bfs", "community"):
        edges, x, labels, order = G.apply_locality_order(
            edges, x, labels,
            method="community" if reorder == "community" else "bfs",
            cache=gc_)
    base = T.hgcn_mode_defaults(
        hgcn.HGCNConfig(feat_dim=x.shape[1], num_classes=0), overrides,
        False)
    cfg = T.apply_overrides(base, T._precision_default(run, overrides))
    num_nodes = x.shape[0]
    mesh = auto_mesh(run.multihost, tp=run.tp)
    if mesh is None:
        raise Refused("train_mesh steps the mesh the trainer picks for "
                      "several devices; it found one device")
    split = G.split_edges(
        edges, num_nodes, x, seed=split_seed,
        cluster_min_pair=G.cluster_min_pair_for(cfg.use_att), cache=gc_)
    mark("setup_order_split_s")
    model, opt, state = hgcn.init_lp(cfg, split.graph, seed=run.seed)
    mark("setup_init_lp_s")
    if inject is not None:
        state = inject(state)
    pairs = hgcn.round_up_pairs(split.train_pos, mesh)
    train_pos = mh.distribute_batch(jnp.asarray(pairs), mesh)
    step, state, ga = hgcn.make_node_sharded_step_lp(
        model, opt, num_nodes, mesh, state, split)
    stepper, spc = T._chunked(run, lambda st: step(st, ga, train_pos))
    mark("setup_partition_s")  # partition_graph and the placement
    return Built(run, state, stepper, spc, split, np.asarray(order), data,
                 marks, np.asarray(pairs), mesh)


def seeded_build(config: dict, traffic: dict, seed: int, data_root: str,
                 made=None):
    """``train_fullgraph.seeded_build`` over this driver's wiring."""
    import jax.numpy as jnp

    t0 = time.perf_counter()
    weights, key = made or one.make_weights(seed, config["model"])
    key.block_until_ready()  # the process's first program on the chip
    t_weights = time.perf_counter() - t0
    run_cfg, overrides = one.program_run_config(config, traffic, seed,
                                                data_root)
    start = {}

    def inject(state):
        start["tree"] = one.to_program_tree(weights, state.params)
        return state._replace(
            params=one.to_program_tree(weights, state.params),
            key=jnp.array(key))

    built = build(run_cfg, overrides, int(config["dataset"]["split_seed"]),
                  inject)
    built.marks["setup_first_program_s"] = t_weights
    return built, start["tree"], weights, key


def unload_programs() -> None:
    """Drop every compiled program of the process.  A loaded step program
    keeps its temporaries reserved on each device (5.4 GB for this
    configuration's, 9.2 GB for its twin's): the twin and the reference
    need that room, and the jitted step that held them is gone."""
    import jax

    gc.collect()
    jax.clear_caches()


def partition_gauges() -> dict:
    """The gauges ``parallel/node_shard.partition_graph`` set, by their
    names less the prefix; empty where the program sets none."""
    from hyperspace_tpu.telemetry import registry

    snap = registry.default_registry().snapshot()
    return {k[len(GAUGES):]: float(v) for k, v in snap.items()
            if k.startswith(GAUGES)}


def graph_facts(built: Built, config: dict) -> dict:
    g, model = built.split.graph, config["model"]
    return {
        "n": int(g.num_nodes), "e": int(np.asarray(g.edge_mask).sum()),
        "widths": [int(model["feat_dim"])] + [int(d) for d in
                                              model["hidden_dims"]],
        "pairs": len(built.pairs) * (1 + int(model["neg_per_pos"])),
        "use_att": bool(model["use_att"]),
        "msg_bytes": 2 if model["agg_dtype"] in ("bfloat16", "float16") else 4,
        "mesh": {k: int(v) for k, v in built.mesh.shape.items()},
    }


def check_batch(pairs, train_pos, mesh_shape: dict) -> None:
    """The batch the program stepped on is the configuration's: the
    training positives (which ``reference_inputs`` has held to the csv's
    split) repeated cyclically up to a multiple of the mesh's data
    extent, every axis but ``model``.  Reckoned here, not taken from the
    program, because the reference is handed this batch."""
    d = int(np.prod([v for k, v in mesh_shape.items() if k != "model"]))
    stated = np.resize(np.asarray(train_pos), (-(-len(train_pos) // d) * d,
                                               2))
    if pairs.shape != stated.shape or not np.array_equal(pairs, stated):
        raise ValueError(
            f"the program's batch {pairs.shape} is not the training "
            f"positives repeated up to a multiple of {d}")


def run(ctx) -> dict:
    from hyperspace_tpu import compile_cache

    guards.require_pallas()
    config, traffic = ctx.config, ctx.traffic
    t_enter = time.perf_counter()
    compile_cache.activate(None)
    try:
        data_root = one.ensure_dataset(config, ctx.cache_root)
    except AttributeError as e:  # a program from before the configuration
        raise Refused(f"the program lacks the configuration's dataset "
                      f"generator: {e}") from None
    built, start_tree, weights, key = seeded_build(config, traffic, ctx.seed,
                                                   data_root)
    if int(np.prod(list(built.mesh.shape.values()))) != ctx.chips:
        raise Refused(f"the trainer's mesh {dict(built.mesh.shape)} does "
                      f"not span the cell's {ctx.chips} chips")
    check_batch(built.pairs, built.split.train_pos, dict(built.mesh.shape))
    check_steps = int(traffic["check_steps"])
    t_built = time.perf_counter()
    state, got = one.first_steps(built, start_tree, check_steps)
    del start_tree
    t_stepped = time.perf_counter()
    state, warm_s = one._loop(built, state, int(traffic["warm_steps"]))
    step_s = warm_s / int(traffic["warm_steps"])
    spc = built.steps_per_call
    total = max(spc, int(math.ceil(ctx.seconds / step_s / spc)) * spc)
    traced = 0
    if ctx.trace:
        traced = max(spc, int(math.ceil(
            float(traffic["trace_seconds"]) / step_s / spc)) * spc)
        traced = min(traced, total)
    compiles0 = one._compiles()
    setup_s = time.perf_counter() - ctx.t_start

    red = None
    seconds = 0.0
    if total - traced > 0:
        state, seconds = one._loop(built, state, total - traced)
    if traced:
        state, t_s, red = one._traced_loop(
            built, state, traced, os.path.join(ctx.cache_root, "trace",
                                               ctx.workload))
        seconds += t_s
    compiled_in_window = one._compiles() - compiles0
    n = built.data["num_nodes"]
    peak, mem_info = one.device_memory_peak()

    gauges = partition_gauges()
    facts = {**graph_facts(built, config), **gauges,
             "steps_traced": traced, "chips": ctx.chips}
    order, train_pos, pairs = built.order, built.split.train_pos, built.pairs
    built_marks = built.marks
    del built, state
    unload_programs()

    t_twin = time.perf_counter()
    twin_cfg, got_twin = one.twin_config(config), None
    if twin_cfg is not None:
        built, start_tree, _, _ = seeded_build(twin_cfg, traffic, ctx.seed,
                                               data_root, (weights, key))
        _, got_twin = one.first_steps(built, start_tree, 1)
        del built, start_tree
        unload_programs()

    t_ref = time.perf_counter()
    x, graph = one.reference_inputs(config, data_root, order, train_pos)
    want = ref.train_steps(weights, key, x, graph, pairs, config["model"],
                           steps=check_steps)
    twin = None
    if twin_cfg is not None:
        twin = (got_twin, ref.train_steps(weights, key, x, graph, pairs,
                                          twin_cfg["model"], steps=1))
    info = {"setup_to_driver_s": t_enter - ctx.t_start, **built_marks,
            "setup_first_steps_s": t_stepped - t_built,
            "setup_warm_loop_s": warm_s, "twin_s": t_ref - t_twin,
            "reference_s": time.perf_counter() - t_ref,
            "window_s": seconds, "steps": total, "steps_traced": traced,
            "step_s_warm": step_s,
            **{"partition_" + k: v for k, v in gauges.items()}}
    checks = correctness.compare_training(got, want, ctx.limits, twin)
    checks.append(("compiles_in_window", compiled_in_window, 0.0))
    return {
        "attempted": total, "failed": 0,
        "end_to_end": {
            "samples_per_s_chip": n * total / seconds / ctx.chips,
            "setup_s": setup_s},
        "memory_peak_bytes": peak, "memory": mem_info, "facts": facts,
        "trace": red, "checks": checks, "info": info,
    }
