"""Driver ``train_moe_lm``: language-model pretraining steps of the MoE LM
(``hyperspace_tpu.models.moe_lm``), stepped by
``hyperspace_tpu.train.loop.run_loop``.

:func:`build` wires configuration, token stream, state and stepper by
the calls ``cli.train.run_moe_lm`` makes (a test pins the two to the
same first-step loss).  What does not depend on the model is
``train_lm``'s and ``train_fullgraph``'s, used as they are: the
configuration's keys as the CLI's, the batches, the loop, the traced
loop, the memory reading, the count of compiles.  :func:`run` follows
``train_lm``'s order:

1. refuses a program without this trainer at once, and anything but
   Pallas kernels,
2. makes the weights from ``--seed`` (the plain reference's initialiser)
   and puts them into the program's state before the step is built,
3. drives the stepper through its first ``check_steps`` steps, reading
   after each its loss and, after the first, the gradient's norm and the
   rows routed to held experts (the state's stats vector) and the clipped
   gradient out of Adam's first moment; then the parameters' change,
4. warms ``run_loop``, 5. times it over the window (and traces its last
   few seconds), 6. reads the device memory and frees the state,
7. builds the configuration's twin (``check_twin``: float32 lanes and
   float32 matmuls in truth), drives its first step and frees it,
8. follows the same steps with the plain reference
   (``benchmark/reference/laguna.py``), computed in blocks, and compares.

A sample is one token with a loss term: ``samples_per_s_chip`` = tokens
a step x optimizer steps completed / the window's seconds / chips.
"""

from __future__ import annotations

import gc
import math
import os
import time

import numpy as np

from benchmark import correctness, costs_moe, guards
from benchmark.drivers import train_fullgraph as one
from benchmark.drivers import train_lm as lm
from benchmark.guards import Refused
from benchmark.reference import laguna as ref

TOP_LEAVES = ("embed", "head", "final_norm")


# --- weights: the reference's names <-> the program's tree -------------------


def _kinds(cfg) -> dict:
    """{kind: its layers, in order} of a MoELMConfig."""
    return {kind: cfg.layers_of(kind) for kind in cfg.kinds()}


def to_program_tree(weights: dict, cfg) -> dict:
    """The harness's weights (``l<i>.<leaf>`` a layer) in the program's
    shape: each kind's leaves stacked over its layers."""
    import jax.numpy as jnp

    tree = {k: jnp.array(weights[k]) for k in TOP_LEAVES}  # copies: donated
    tree["kinds"] = {}
    want = set(TOP_LEAVES)
    for kind, layers in _kinds(cfg).items():
        names = sorted({k.split(".", 1)[1] for k in weights
                        if k.startswith(f"l{layers[0]}.")})
        want |= {f"l{i}.{n}" for i in layers for n in names}
        tree["kinds"][kind] = {n: jnp.stack([weights[f"l{i}.{n}"]
                                             for i in layers])
                               for n in names}
    if set(weights) != want:
        raise Refused(f"the weights {sorted(set(weights) ^ want)} are not "
                      "the configuration's")
    return tree


def from_program_tree(tree, cfg) -> dict:
    """{reference name: host array} of a tree in the program's shape."""
    out = {k: np.asarray(tree[k]) for k in TOP_LEAVES}
    for kind, layers in _kinds(cfg).items():
        for name, stacked in tree["kinds"][kind].items():
            stacked = np.asarray(stacked)
            out.update({f"l{i}.{name}": stacked[j]
                        for j, i in enumerate(layers)})
    return out


# --- the program, wired as cli.train.run_moe_lm wires it ----------------------


def build(config: dict, traffic: dict, seed: int, data_root: str,
          params=None) -> lm.Built:
    """``cli.train.run_moe_lm``, call for call, up to the step loop;
    ``params`` (a tree in the program's shape) takes the place of the
    seed's own initial parameters."""
    import jax.numpy as jnp

    from hyperspace_tpu.cli import train as T
    from hyperspace_tpu.data import text
    from hyperspace_tpu.models import moe_lm

    marks, t0 = {}, time.perf_counter()
    pairs = (lm.program_pairs(config) + list(traffic.get("run_overrides", []))
             + [f"seed={int(seed)}", f"data_root={data_root}"])
    run, overrides = T.split_overrides(pairs, T.RunConfig())
    T._reject_accum(run, "moe_lm")
    cfg, stream_kw = T._moe_lm_config(run, overrides)
    tokens, source = text.load_token_stream(
        run.data_root, vocab_size=cfg.vocab_size, **stream_kw)
    if source != "disk":
        raise Refused(f"no token stream under {data_root}")
    marks["setup_load_stream_s"] = time.perf_counter() - t0
    data = {"dataset": "token_stream", "source": source,
            "num_tokens": int(tokens.size),
            "tokens_per_step": cfg.sequence_length * cfg.sequences_per_step}
    opt, state = moe_lm.init_state(cfg, seed=run.seed, params=params)
    stream = jnp.asarray(tokens, jnp.int32)
    if run.scan_chunk > 1:
        run = T._chunk_run(run)
    stepper, spc = T._chunked(
        run, lambda st: moe_lm.train_step(cfg, opt, st, stream))
    marks["setup_init_state_s"] = (time.perf_counter() - t0
                                   - marks["setup_load_stream_s"])
    return lm.Built(run, cfg, state, stepper, spc, tokens, data, marks)


def program_config(config: dict):
    """The MoELMConfig the CLI makes of the configuration."""
    from hyperspace_tpu.cli import train as T

    run, overrides = T.split_overrides(lm.program_pairs(config),
                                       T.RunConfig())
    return T._moe_lm_config(run, overrides)[0]


def reference_opt(config: dict) -> dict:
    r = config["recipe"]
    return {"lr": r["lr"], "b1": r["adam_b1"], "b2": r["adam_b2"],
            "eps": r["adam_eps"], "weight_decay": r["weight_decay"],
            "clip_norm": r["clip_norm"]}


def make_weights(seed: int, config: dict) -> dict:
    return ref.init_weights(int(seed), lm.model_of(config), config["job"],
                            std=float(config["recipe"]["init_std"]))


def seeded_build(config: dict, traffic: dict, seed: int, data_root: str):
    """(built, host copy of the start): the program wired for the cell
    with the harness's weights from ``seed`` in its state."""
    import jax

    t0 = time.perf_counter()
    weights = make_weights(seed, config)
    start = {k: np.asarray(v) for k, v in weights.items()}
    tree = to_program_tree(weights, program_config(config))
    del weights
    jax.block_until_ready(tree)
    t_weights = time.perf_counter() - t0
    built = build(config, traffic, seed, data_root, params=tree)
    built.marks["setup_weights_s"] = t_weights
    return built, start


# --- readings of the first steps ---------------------------------------------


def first_steps(built: lm.Built, start, steps: int, b1: float) -> tuple:
    """Drive the stepper through its first ``steps`` steps.  Returns
    (state, readings): each loss; of the first step the gradient's norm,
    the rows routed to held experts a sparse layer and the clipped
    gradient as Adam got it (mu_1 = (1 - b1) g); the per-leaf norm of the
    parameters' change (``start`` None: not read)."""
    from hyperspace_tpu.models import moe_lm

    if built.steps_per_call != 1:
        raise Refused("the first steps are read one by one: a chunked "
                      "stepper (scan_chunk > 1) needs a driver of its own")
    state, got = built.state, {"losses": []}
    for i in range(steps):
        state, loss = built.stepper(state)
        got["losses"].append(float(loss))
        if i == 0:
            stats = moe_lm.read_stats(built.cfg, state.stats)
            mu = one._adam_mu(state.opt_state)
            if mu is None:
                raise Refused("no Adam first moment in the optimizer state")
            got.update(grad_norm=stats["grad_norm"],
                       held_rows=stats["held_rows"],
                       grads={k: v / np.float32(1.0 - b1) for k, v in
                              from_program_tree(mu, built.cfg).items()})
    if start is not None:
        end = from_program_tree(state.params, built.cfg)
        got["change_norms"] = lm._leaf_norms({k: end[k] - start[k]
                                              for k in end})
    return state, got


def first_step_of_twin(config: dict, traffic: dict, seed: int,
                       data_root: str):
    """The twin's readings of its first step (None: no twin stated), its
    state freed again."""
    twin_cfg = lm.twin_config(config)
    if twin_cfg is None:
        return None
    built, _ = seeded_build(twin_cfg, traffic, seed, data_root)
    _, got = first_steps(built, None, 1, float(config["recipe"]["adam_b1"]))
    del built
    gc.collect()
    return got


# --- the comparison -----------------------------------------------------------


def gaps(got: dict, want: dict) -> dict:
    """Every number the cell's limits may name."""
    out = {f"loss_gap_step{i}": lm._rel(a, b) for i, (a, b) in enumerate(
        zip(got["losses"], want["losses"]), 1)}
    out["grad_norm_gap"] = lm._rel(got["grad_norm"], want["grad_norm"])
    # a routing that differs moves rows between experts: the worst
    # layer's count of rows routed to held experts
    rows = list(zip(got["held_rows"], want["held_rows"]))
    out["held_rows_gap"] = (max(lm._rel(a, b) for a, b in rows)
                            if len(got["held_rows"]) == len(want["held_rows"])
                            else float("nan"))
    clipped = {k: np.asarray(v) * np.float32(
        min(1.0, want["clip_norm"] / want["grad_norm"]))
        for k, v in want["grads"].items()}
    out["grad_difference"] = correctness.worst_leaf_difference(
        got["grads"], clipped)
    if "change_norms" in got:   # the twin's one step reads none
        moved = correctness.moved_leaves(lm._leaf_norms(clipped))
        out["change_norm_gap"] = correctness.worst_leaf_gap(
            got["change_norms"], want["change_norms"], moved)
    return out


def twin_gaps(got: dict, want: dict) -> dict:
    """The twin's first step against the same reference: at float32 lanes
    the program sits within rounding of the reference, and anything
    computed a step of precision down stands clear (PERF.md)."""
    found = gaps(got, want)
    return {"twin_loss_gap": found["loss_gap_step1"],
            "twin_grad_norm_gap": found["grad_norm_gap"],
            "twin_grad_difference": found["grad_difference"]}


def compare(got: dict, want: dict, limits: dict, got_twin=None) -> list:
    """[(name, value, limit)] for every number the cell's limits name."""
    found = gaps(got, want)
    if got_twin is not None:
        found.update(twin_gaps(got_twin, want))
    return [(name, found[name], float(limit))
            for name, limit in limits.items()]


def follow_reference(config: dict, batches: list, seed: int, *,
                     blocked: bool, **kw) -> dict:
    """The plain reference's readings of the same steps from the same
    seed (``kw``: the control's dtype, a planted fault)."""
    opt = reference_opt(config)
    want = ref.train_steps(
        make_weights(seed, config), batches, lm.model_of(config),
        config["job"], opt, blocked=blocked,
        remake=lambda: make_weights(seed, config), **kw)
    want["clip_norm"] = opt["clip_norm"]
    return want


# --- one run -------------------------------------------------------------


def cell_facts(config: dict, held_rows=None, held_experts=None) -> dict:
    """What the readers need of the cell: the FLOPs one step requires
    (the routed experts over ``held_rows``, the step's rows of each
    sparse layer; the expected count where None), the shapes, and what
    one grouped-matmul call (a sparse layer, one sequence) has to do on
    average: its rows and the held experts that got any
    (``held_experts``, each sparse layer's; all held where None)."""
    model, job = lm.model_of(config), config["job"]
    seq, rows = int(job["sequence_length"]), int(job["sequences_per_step"])
    heads = [h for h, t in zip(model["num_attention_heads_per_layer"],
                               model["layer_types"][
                                   :int(model["num_hidden_layers"])])
             if t == "sliding_attention"]
    sparse = model["mlp_layer_types"][
        :int(model["num_hidden_layers"])].count("sparse")
    if held_rows is None:
        held_rows = [rows * costs_moe.expected_rows(model, job, seq)] * sparse
    if held_experts is None:
        held_experts = [rows * float(model["num_experts"])] * sparse
    calls = max(sparse, 1) * rows
    facts = {"step_flops": costs_moe.step_flops(model, job, seq, rows,
                                                held_rows),
             "seq": seq, "head_dim": int(model["head_dim"]),
             "kv_heads": int(model["num_key_value_heads"]),
             "window": int(model["sliding_window"]),
             "itemsize": 2 if job["precision"] == "bf16" else 4,
             "hidden": int(model["hidden_size"]),
             "moe_width": int(model["moe_intermediate_size"]),
             "held_rows_sum": float(sum(held_rows)),
             "held_rows_mean": float(sum(held_rows)) / calls,
             "held_experts_mean": float(sum(held_experts)) / calls}
    if heads:
        facts["sliding_heads"] = int(heads[0])
    return facts


def run(ctx) -> dict:
    import importlib.util

    from hyperspace_tpu import compile_cache

    if importlib.util.find_spec("hyperspace_tpu.models.moe_lm") is None:
        # a checkout from before the trainer: say so at once, run nothing
        raise Refused("the program has no moe_lm trainer "
                      "(hyperspace_tpu/models/moe_lm.py)")
    from hyperspace_tpu.models import moe_lm

    guards.require_pallas()
    config, traffic = ctx.config, ctx.traffic
    if ctx.chips != 1:
        raise Refused("train_moe_lm steps one chip")
    t_enter = time.perf_counter()
    compile_cache.activate(None)
    data_root = os.path.dirname(one.ensure_dataset(config, ctx.cache_root))
    built, start = seeded_build(config, traffic, ctx.seed, data_root)
    check_steps = int(traffic["check_steps"])
    t_built = time.perf_counter()
    state, got = first_steps(built, start, check_steps,
                             float(config["recipe"]["adam_b1"]))
    del start
    t_stepped = time.perf_counter()
    state, warm_s = one._loop(built, state, int(traffic["warm_steps"]))
    step_s = warm_s / int(traffic["warm_steps"])
    total = max(1, int(math.ceil(ctx.seconds / step_s)))
    traced = 0
    if ctx.trace:
        traced = min(total, max(1, int(math.ceil(
            float(traffic["trace_seconds"]) / step_s))))
    compiles0 = one._compiles()
    setup_s = time.perf_counter() - ctx.t_start

    red, seconds = None, 0.0
    if total - traced > 0:
        state, seconds = one._loop(built, state, total - traced)
    if traced:
        state, t_s, red = one._traced_loop(
            built, state, traced, os.path.join(ctx.cache_root, "trace",
                                               ctx.workload))
        seconds += t_s
    compiled_in_window = one._compiles() - compiles0
    peak, mem_info = one.device_memory_peak()
    last = moe_lm.read_stats(built.cfg, state.stats)
    last_rows = last["held_rows"]

    facts = dict(cell_facts(config, last_rows, last["held_experts"]),
                 steps_traced=traced, chips=ctx.chips)
    tokens_per_step = built.data["tokens_per_step"]
    batches = lm.host_batches(built.tokens, check_steps,
                              built.cfg.sequence_length,
                              built.cfg.sequences_per_step)
    marks = built.marks
    del built, state
    gc.collect()

    t_twin = time.perf_counter()
    got_twin = first_step_of_twin(config, traffic, ctx.seed, data_root)
    t_ref = time.perf_counter()
    want = follow_reference(config, batches, ctx.seed, blocked=True)
    info = {"setup_to_driver_s": t_enter - ctx.t_start, **marks,
            "setup_first_steps_s": t_stepped - t_built,
            "setup_warm_loop_s": warm_s, "twin_s": t_ref - t_twin,
            "reference_s": time.perf_counter() - t_ref,
            "window_s": seconds, "steps": total, "steps_traced": traced,
            "step_s_warm": step_s, "held_rows_step1": sum(got["held_rows"]),
            "held_rows_last": sum(last_rows)}
    checks = compare(got, want, ctx.limits, got_twin)
    checks.append(("compiles_in_window", compiled_in_window, 0.0))
    return {
        "attempted": total, "failed": 0,
        "end_to_end": {
            "samples_per_s_chip": tokens_per_step * total / seconds
            / ctx.chips,
            "setup_s": setup_s},
        "memory_peak_bytes": peak, "memory": mem_info, "facts": facts,
        "trace": red, "checks": checks, "info": info,
    }

