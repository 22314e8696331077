"""Driver ``train_fullgraph``: full-graph HGCN link-prediction training,
stepped by ``hyperspace_tpu.train.loop.run_loop``.

:func:`build` wires data, model, state and stepper by the calls
``cli.train.run_hgcn`` makes for a full-graph LP run (a test pins the
two to the same first-step loss); it cannot call ``run_hgcn`` itself,
which has no time limit and ends in an evaluation.  :func:`run` then

1. refuses anything but Pallas kernels,
2. makes the weights and the step key from ``--seed`` and puts them into
   the program's state before the step is built,
3. drives the one stepper through its first ``check_steps`` steps, reading
   each loss, the first gradient out of Adam's first moment, and the
   parameters' change,
4. warms ``run_loop`` itself for a few steps (which also gives the step
   time that sizes the window),
5. times ``run_loop`` over the window (and, with ``--trace 1``, traces
   its last few seconds),
6. reads the device memory and frees the program's state,
7. builds the configuration's twin (``check_twin``: the same program with
   its two lanes stated as float32; benchmark/correctness.py says why),
   drives its first step and frees it,
8. follows the same steps with the plain reference and compares.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib
import json
import math
import os
import shutil
import time

import numpy as np

from benchmark import correctness, guards, trace_reduce
from benchmark.guards import Refused
from benchmark.reference import hgcn as ref


# --- weights and names ---------------------------------------------------


def make_weights(seed: int, model: dict):
    """(weights by the reference's names, step key), on the device, in
    one jitted call from the seed: Glorot-uniform kernels and attention
    vectors, zero biases, the decoder's stated r and t."""
    import jax
    import jax.numpy as jnp

    widths = [int(model["feat_dim"])] + [int(d) for d in
                                         model["hidden_dims"]]

    def glorot(key, shape):
        lim = math.sqrt(6.0 / (shape[0] + shape[1]))
        return jax.random.uniform(key, shape, jnp.float32, -lim, lim)

    @jax.jit
    def make(key):
        out = {}
        k_step, key = jax.random.split(key)
        for i, (a, b) in enumerate(zip(widths[:-1], widths[1:])):
            key, kw, ks, kd = jax.random.split(key, 4)
            out[f"W{i}"] = glorot(kw, (a, b))
            out[f"b{i}"] = jnp.zeros((b,), jnp.float32)
            if model["use_att"]:
                out[f"a_src{i}"] = glorot(ks, (b, 1))
                out[f"a_dst{i}"] = glorot(kd, (b, 1))
        out["r"] = jnp.asarray(model["decoder_r_init"], jnp.float32)
        out["t_raw"] = jnp.asarray(
            math.log(math.expm1(model["decoder_t_init"])), jnp.float32)
        return out, k_step

    return make(jax.random.PRNGKey(int(seed)))


_LEAF = {"kernel": "W", "bias": "b", "att_src": "a_src", "att_dst": "a_dst"}


def program_names(params) -> dict:
    """{reference name: path in the program's parameter tree}."""
    out = {}
    for conv, leaves in params["encoder"].items():
        i = int(conv.removeprefix("conv"))
        for leaf in leaves:
            out[f"{_LEAF[leaf]}{i}"] = ("encoder", conv, leaf)
    for leaf in params["decoder"]:
        out[leaf] = ("decoder", leaf)
    return out


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def to_program_tree(weights: dict, like) -> dict:
    """The harness's weights in the shape of the program's tree; every
    leaf has to be there with the program's own shape and type."""
    import jax
    import jax.numpy as jnp

    names = program_names(like)
    if sorted(names) != sorted(weights):
        raise Refused(f"the program's parameters {sorted(names)} are not "
                      f"the configuration's {sorted(weights)}")
    out = jax.tree_util.tree_map(lambda a: a, like)  # fresh containers
    for name, path in names.items():
        old = _get(like, path)
        new = jnp.array(weights[name], dtype=old.dtype)  # a copy: donated
        if new.shape != old.shape:
            raise Refused(f"{name}: the program holds {old.shape}, the "
                          f"configuration states {new.shape}")
        _get(out, path[:-1])[path[-1]] = new
    return out


def _adam_mu(opt_state):
    """Adam's first moment, wherever the optimizer chain keeps it."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for part in opt_state:
            mu = _adam_mu(part)
            if mu is not None:
                return mu
    return None


# --- the program, wired as cli.train.run_hgcn wires it ---------------------


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


@functools.lru_cache(maxsize=1)
def _load_graph(dataset: str, data_root: str):
    """``G.load_graph``, once a process: the twin is wired from the same
    arrays."""
    from hyperspace_tpu.data import graphs as G

    return G.load_graph(dataset, data_root)


def build(run, overrides: dict, split_seed: int, inject=None) -> Built:
    """The full-graph LP branch of ``cli.train.run_hgcn``, call for call,
    up to the step loop.  ``inject(state) -> state`` runs between
    ``init_lp`` and the step builder (which places the state)."""
    import jax.numpy as jnp

    from hyperspace_tpu.cli import train as T
    from hyperspace_tpu.data import graphs as G
    from hyperspace_tpu.models import hgcn
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
        raise Refused("train_fullgraph drives the full-graph LP step only")
    edges, x, labels, ncls, source = _load_graph(dataset, run.data_root)
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
    if auto_mesh(run.multihost, tp=run.tp) is not None:
        raise Refused("train_fullgraph steps one chip; the trainer would "
                      "pick a mesh here, which needs a driver of its own")
    split = G.split_edges(
        edges, num_nodes, x, seed=split_seed,
        cluster_min_pair=G.cluster_min_pair_for(cfg.use_att), cache=gc_)
    mark("setup_order_split_s")
    model, opt, state = hgcn.init_lp(cfg, split.graph, seed=run.seed)
    mark("setup_init_lp_s")
    if inject is not None:
        state = inject(state)
    ga = hgcn._device_graph(split.graph)
    train_pos = jnp.asarray(split.train_pos)
    stepper, spc = T._chunked(
        run, lambda st: hgcn.train_step_lp(model, opt, num_nodes, st, ga,
                                           train_pos))
    mark("setup_place_s")
    return Built(run, state, stepper, spc, split, np.asarray(order), data,
                 marks)


def twin_config(config: dict):
    """The configuration with its ``check_twin`` laid over it, or None."""
    twin = config.get("check_twin")
    if not twin:
        return None
    return dict(config, check_twin=None,
                cli_overrides=list(config["cli_overrides"])
                + list(twin["cli_overrides"]),
                model={**config["model"], **twin["model"]})


def seeded_build(config: dict, traffic: dict, seed: int, data_root: str,
                 made=None):
    """(built, start tree, weights, key): the program wired for one cell
    with the harness's weights and step key from ``seed`` in its state
    (``made``: the pair already made for this seed).  The start tree is
    a second copy of the weights in the program's shape (the program
    donates its own at the first step)."""
    import jax.numpy as jnp

    t0 = time.perf_counter()
    weights, key = made or make_weights(seed, config["model"])
    key.block_until_ready()  # the process's first program on the chip
    t_weights = time.perf_counter() - t0
    run_cfg, overrides = program_run_config(config, traffic, seed, data_root)
    start = {}

    def inject(state):
        start["tree"] = to_program_tree(weights, state.params)
        return state._replace(
            params=to_program_tree(weights, state.params),
            key=jnp.array(key))

    built = build(run_cfg, overrides, int(config["dataset"]["split_seed"]),
                  inject)
    built.marks["setup_first_program_s"] = t_weights
    return built, start["tree"], weights, key


def program_run_config(config: dict, traffic: dict, seed: int,
                       data_root: str):
    """(RunConfig, workload overrides) from the configuration's and the
    traffic's ``key=value`` strings, split as ``cli.train.main`` splits
    them."""
    from hyperspace_tpu.cli import train as T

    pairs = (list(config["cli_overrides"])
             + list(traffic.get("run_overrides", []))
             + [f"seed={int(seed)}", f"data_root={data_root}"])
    return T.split_overrides(pairs, T.RunConfig())


def ensure_dataset(config: dict, cache_root: str) -> str:
    ds = config["dataset"]
    mod, fn = ds["generator"].rsplit(".", 1)
    tag = json.dumps(ds.get("generator_args", {}), sort_keys=True)
    name = f"{ds['name']}-seed{ds['generator_seed']}"
    if ds.get("generator_args"):
        import hashlib

        name += "-" + hashlib.sha256(tag.encode()).hexdigest()[:8]
    root = os.path.join(cache_root, "data", name)
    return getattr(importlib.import_module(mod), fn)(
        root, seed=int(ds["generator_seed"]), **ds.get("generator_args", {}))


# --- readings of the program's first steps -----------------------------------


def _leaves(tree, names: dict, scale: float = 1.0) -> tuple:
    """({name: norm}, {name: array}) of a tree in the program's shape."""
    out = {n: np.asarray(_get(tree, p), np.float32) * np.float32(scale)
           for n, p in names.items()}
    return ({n: float(np.sqrt(np.sum(np.square(a, dtype=np.float64))))
             for n, a in out.items()}, out)


def first_steps(built: Built, start_tree, steps: int) -> tuple:
    """Drive the stepper through its first ``steps`` steps.  Returns
    (state, readings): each loss, the first gradient's per-leaf norm as
    Adam got it (mu_1 = (1 - b1) g), the per-leaf norm of the change."""
    import jax

    if built.steps_per_call != 1:
        raise Refused("the first steps are read one by one: a chunked "
                      "stepper (scan_chunk > 1) needs a driver of its own")
    names = program_names(start_tree)
    state, losses, grad_norms, grads = built.state, [], None, None
    for i in range(steps):
        state, loss = built.stepper(state)
        losses.append(loss)
        if i == 0:
            mu = _adam_mu(state.opt_state)
            if mu is None:
                raise Refused("no Adam first moment in the optimizer state")
            grad_norms, grads = _leaves(mu, names, 1.0 / (1.0 - ref.ADAM_B1))
    diff = jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda u, v: u - v, a, b))(state.params, start_tree)
    change_norms, _ = _leaves(diff, names)
    return state, {"losses": [float(v) for v in losses],
                   "grad_norms": grad_norms, "change_norms": change_norms,
                   "grads": grads}


# --- the reference's side ----------------------------------------------------


def _read_csv(path, dtype):
    import pandas as pd

    return pd.read_csv(path, header=None, dtype=dtype).to_numpy()


def reference_inputs(config: dict, data_root: str, order, train_pos):
    """The raw dataset read again from disk, relabeled by the (checked)
    order, and the message graph built from the (checked) pairs."""
    ds = config["dataset"]
    raw = os.path.join(data_root, "raw")
    edges = _read_csv(os.path.join(raw, "edge.csv"), np.int64)
    x = np.ascontiguousarray(
        _read_csv(os.path.join(raw, "node-feat.csv"), np.float32))
    n = x.shape[0]
    ref.check_permutation(order, n)
    ref.check_training_pairs(train_pos, edges, order, n, ds["val_frac"],
                             ds["test_frac"])
    return x[np.asarray(order)], ref.message_graph(train_pos, n)



# --- one run -------------------------------------------------------------


def _graph_facts(built: Built, config: dict) -> dict:
    g = built.split.graph
    model = config["model"]
    e = int(np.asarray(g.edge_mask).sum())
    cs = g.cluster_split
    e_block = 0 if cs is None else int(len(cs.c_recv))
    return {
        "n": int(g.num_nodes), "e": e, "e_block": e_block,
        "e_rest": e - e_block,
        "widths": [int(model["feat_dim"])] + [int(d) for d in
                                              model["hidden_dims"]],
        "pairs": len(built.split.train_pos)
        * (1 + int(model["neg_per_pos"])),
        "use_att": bool(model["use_att"]),
        "msg_bytes": 2 if model["agg_dtype"] in ("bfloat16", "float16") else 4,
    }


def _loop(built: Built, state, steps: int, **run_kw):
    """One ``run_loop`` call over ``steps`` steps, closed by a block on
    the last loss.  Returns (state, seconds)."""
    import jax

    from hyperspace_tpu.train.loop import run_loop

    run = dataclasses.replace(built.run, steps=int(steps), **run_kw)
    t0 = time.perf_counter()
    state, loss = run_loop(run, state, built.stepper,
                           steps_per_call=built.steps_per_call,
                           data=built.data)
    jax.block_until_ready(loss)
    return state, time.perf_counter() - t0


def device_memory_peak() -> tuple:
    """(peak bytes on the fullest chip, the readings it was made from),
    all from the device's own counters (``memory_stats()``), read while
    the step program is loaded.

    ``peak_bytes_in_use`` counts arrays and not what a loaded program
    holds for its temporaries (PR 21's finding; here it is set before
    the first step, by ``init_lp``).  The runtime counts that apart, as
    ``bytes_reserved``: the step program reserves its temporaries "at the
    bottom of memory" for as long as it is loaded, and a step is refused
    for memory where less than that is free (PERF.md, PR 25's ballast
    run).  So the peak is the larger of the allocator's own peak and of
    what is held while a step runs: bytes in use plus bytes reserved.
    """
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    alloc_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in stats)
    held = max(stats, key=lambda m: int(m.get("bytes_in_use", 0))
               + int(m.get("bytes_reserved", 0)))
    live = int(held.get("bytes_in_use", 0))
    reserved = int(held.get("bytes_reserved", 0))
    return max(alloc_peak, live + reserved), {
        "allocator_peak_bytes": alloc_peak, "live_bytes": live,
        "reserved_bytes": reserved}


def _compiles() -> float:
    from hyperspace_tpu.telemetry import registry

    return float(registry.default_registry().snapshot().get(
        "jax/recompiles", 0))


def _traced_loop(built, state, steps, trace_dir):
    """``run_loop`` under the profiler, between the window's two
    annotations, with the program's host spans kept beside it."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    chrome = os.path.join(trace_dir, "host_spans.json")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        anchor = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_BEGIN):
            pass
        state, seconds = _loop(built, state, steps, trace_out=chrome)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_END):
            pass
    finally:
        jax.profiler.stop_trace()
    red = trace_reduce.reduce_file(trace_reduce.newest_xplane(trace_dir),
                                   anchor_perf_s=anchor, chrome_path=chrome)
    return state, seconds, red


def run(ctx) -> dict:
    from hyperspace_tpu import compile_cache

    guards.require_pallas()
    config, traffic = ctx.config, ctx.traffic
    t_enter = time.perf_counter()
    compile_cache.activate(None)
    data_root = ensure_dataset(config, ctx.cache_root)
    built, start_tree, weights, key = seeded_build(config, traffic, ctx.seed,
                                                   data_root)
    if ctx.chips != 1:
        raise Refused("train_fullgraph steps one chip")
    check_steps = int(traffic["check_steps"])
    t_built = time.perf_counter()
    state, got = first_steps(built, start_tree, check_steps)
    del start_tree
    t_stepped = time.perf_counter()
    state, warm_s = _loop(built, state, int(traffic["warm_steps"]))
    step_s = warm_s / int(traffic["warm_steps"])
    spc = built.steps_per_call
    total = max(spc, int(math.ceil(ctx.seconds / step_s / spc)) * spc)
    traced = 0
    if ctx.trace:
        traced = max(spc, int(math.ceil(
            float(traffic["trace_seconds"]) / step_s / spc)) * spc)
        traced = min(traced, total)
    compiles0 = _compiles()
    setup_s = time.perf_counter() - ctx.t_start

    red = None
    seconds = 0.0
    if total - traced > 0:
        state, seconds = _loop(built, state, total - traced)
    if traced:
        state, t_s, red = _traced_loop(
            built, state, traced, os.path.join(ctx.cache_root, "trace",
                                               ctx.workload))
        seconds += t_s
    compiled_in_window = _compiles() - compiles0
    n = built.data["num_nodes"]
    peak, mem_info = device_memory_peak()

    facts = _graph_facts(built, config)
    facts.update(steps_traced=traced, chips=ctx.chips)
    order, train_pos = built.order, built.split.train_pos
    built_marks = built.marks
    del built, state
    gc.collect()

    t_twin = time.perf_counter()
    twin_cfg, got_twin = twin_config(config), None
    if twin_cfg is not None:
        built, start_tree, _, _ = seeded_build(twin_cfg, traffic, ctx.seed,
                                               data_root, (weights, key))
        _, got_twin = first_steps(built, start_tree, 1)
        del built, start_tree
        gc.collect()

    t_ref = time.perf_counter()
    x, graph = reference_inputs(config, data_root, order, train_pos)
    want = ref.train_steps(weights, key, x, graph, train_pos,
                           config["model"], steps=check_steps)
    twin = None
    if twin_cfg is not None:
        twin = (got_twin, ref.train_steps(weights, key, x, graph, train_pos,
                                          twin_cfg["model"], steps=1))
    info = {"setup_to_driver_s": t_enter - ctx.t_start, **built_marks,
            "setup_first_steps_s": t_stepped - t_built,
            "setup_warm_loop_s": warm_s, "twin_s": t_ref - t_twin,
            "reference_s": time.perf_counter() - t_ref,
            "window_s": seconds, "steps": total, "steps_traced": traced,
            "step_s_warm": step_s}
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
