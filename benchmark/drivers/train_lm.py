"""Driver ``train_lm``: language-model pretraining steps of the looped
model (``hyperspace_tpu.models.looplm``), stepped by
``hyperspace_tpu.train.loop.run_loop``.

:func:`build` wires configuration, token stream, state and stepper by
the calls ``cli.train.run_looplm`` makes (a test pins the two to the same
first-step loss); it cannot call ``run_looplm`` itself, which has no
time limit.  What does not depend on the kind of step is
``train_fullgraph``'s, used as it is: the loop, the traced loop, the
memory reading, the count of compiles.  :func:`run` follows that
driver's order:

1. refuses anything but Pallas kernels,
2. makes the weights from ``--seed`` (the plain reference's initialiser)
   and puts them into the program's state before the step is built,
3. drives the one stepper through its first ``check_steps`` steps,
   reading after each its loss and, after the first, the per-pass
   cross-entropies, the mean exit probabilities, the gradient's norm
   (all out of the state's stats vector) and the clipped gradient out of
   Adam's first moment; then the parameters' change against a host copy
   of the start (no second copy of the weights stays on the device),
4. warms ``run_loop`` for a few steps, 5. times it over the window (and
   traces its last few seconds), 6. reads the device memory and frees
   the program's state,
7. builds the configuration's twin (``check_twin``: the same program
   with its lanes stated as float32 and float32 matmuls in truth;
   :func:`twin_gaps` says why), drives its first step and frees it,
8. follows the same steps with the plain reference, computed in blocks
   from weights made again from the seed, and compares.

A sample is one token with a loss term: ``samples_per_s_chip`` = tokens
a step x optimizer steps completed / the window's seconds / chips.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import time

import numpy as np

from benchmark import correctness, costs_lm, guards
from benchmark.drivers import train_fullgraph as one
from benchmark.guards import Refused
from benchmark.reference import looplm as ref

LAYER_LEAVES = ref.LAYER_MATS + ref.LAYER_GAINS
TOP_LEAVES = ("embed", "head", "gate_w", "gate_b", "final_norm")


# --- weights: the reference's names <-> the program's tree -------------------


def to_program_tree(weights: dict, n_layers: int) -> dict:
    """The harness's weights (``l<i>.<leaf>`` a layer) in the program's
    shape: the layers' leaves stacked ``[L, ...]``."""
    import jax.numpy as jnp

    want = set(TOP_LEAVES) | {f"l{i}.{n}" for i in range(n_layers)
                              for n in LAYER_LEAVES}
    if set(weights) != want:
        raise Refused(f"the weights {sorted(set(weights) ^ want)} are not "
                      "the configuration's")
    tree = {k: jnp.array(weights[k]) for k in TOP_LEAVES}  # copies: donated
    tree["layers"] = {n: jnp.stack([weights[f"l{i}.{n}"]
                                    for i in range(n_layers)])
                      for n in LAYER_LEAVES}
    return tree


def from_program_tree(tree) -> dict:
    """{reference name: host array} of a tree in the program's shape."""
    out = {k: np.asarray(tree[k]) for k in TOP_LEAVES}
    for n in LAYER_LEAVES:
        stacked = np.asarray(tree["layers"][n])
        out.update({f"l{i}.{n}": stacked[i] for i in range(len(stacked))})
    return out


# --- the program, wired as cli.train.run_looplm wires it -----------------------


@dataclasses.dataclass
class Built:
    run: object          # cli.train.RunConfig
    cfg: object          # models.looplm.LoopLMConfig
    state: object
    stepper: object
    steps_per_call: int
    tokens: np.ndarray   # the stream, host
    data: dict
    marks: dict          # seconds each part of the wiring took


# the groups the configuration's file adds; every other top-level key is
# the published config.json's, which the file holds at the top level, key
# for key as its source does
_FILE_KEYS = frozenset({
    "name", "source", "paper", "program_yaml", "published", "reduced",
    "deployment", "job", "recipe", "dataset", "check_twin", "tiny",
    "assumed"})


def model_of(config: dict) -> dict:
    """The model's keys as published (but for the cut ``reduced`` names)."""
    return {k: v for k, v in config.items() if k not in _FILE_KEYS}


def program_pairs(config: dict) -> list:
    """The configuration's model, job and recipe as the CLI's
    ``key=value`` strings, formed as ``cli.train.main`` forms a yaml's."""
    doc = {**model_of(config), **config["job"], **config["recipe"]}
    return [f"{k}={json.dumps(v) if isinstance(v, list) else v}"
            for k, v in doc.items()]


def build(config: dict, traffic: dict, seed: int, data_root: str,
          params=None) -> Built:
    """``cli.train.run_looplm``, call for call, up to the step loop;
    ``params`` (a tree in the program's shape) takes the place of the
    seed's own initial parameters."""
    import jax.numpy as jnp

    from hyperspace_tpu.cli import train as T
    from hyperspace_tpu.data import text
    from hyperspace_tpu.models import looplm

    marks, t0 = {}, time.perf_counter()
    pairs = (program_pairs(config) + list(traffic.get("run_overrides", []))
             + [f"seed={int(seed)}", f"data_root={data_root}"])
    run, overrides = T.split_overrides(pairs, T.RunConfig())
    T._reject_accum(run, "looplm")
    cfg, stream_kw = T._looplm_config(run, overrides)
    tokens, source = text.load_token_stream(
        run.data_root, vocab_size=cfg.vocab_size, **stream_kw)
    if source != "disk":
        raise Refused(f"no token stream under {data_root}")
    marks["setup_load_stream_s"] = time.perf_counter() - t0
    data = {"dataset": "token_stream", "source": source,
            "num_tokens": int(tokens.size),
            "tokens_per_step": cfg.sequence_length * cfg.sequences_per_step}
    opt, state = looplm.init_state(cfg, seed=run.seed, params=params)
    stream = jnp.asarray(tokens, jnp.int32)
    if run.scan_chunk > 1:
        run = T._chunk_run(run)
    stepper, spc = T._chunked(
        run, lambda st: looplm.train_step(cfg, opt, st, stream))
    marks["setup_init_state_s"] = (time.perf_counter() - t0
                                   - marks["setup_load_stream_s"])
    return Built(run, cfg, state, stepper, spc, tokens, data, marks)


def reference_model(config: dict) -> tuple:
    """(model, optimizer) as the plain reference takes them."""
    r = config["recipe"]
    opt = {"lr": r["lr"], "b1": r["adam_b1"], "b2": r["adam_b2"],
           "eps": r["adam_eps"], "weight_decay": r["weight_decay"],
           "clip_norm": r["clip_norm"], "beta": r["entropy_beta"]}
    return model_of(config), opt


def make_weights(seed: int, config: dict) -> dict:
    return ref.init_weights(int(seed), model_of(config),
                            std=float(config["recipe"]["init_std"]))


def seeded_build(config: dict, traffic: dict, seed: int, data_root: str):
    """(built, host copy of the start): the program wired for the cell
    with the harness's weights from ``seed`` in its state."""
    import jax

    t0 = time.perf_counter()
    weights = make_weights(seed, config)
    start = {k: np.asarray(v) for k, v in weights.items()}
    tree = to_program_tree(weights,
                           int(config["num_hidden_layers"]))
    del weights
    jax.block_until_ready(tree)
    t_weights = time.perf_counter() - t0
    built = build(config, traffic, seed, data_root, params=tree)
    built.marks["setup_weights_s"] = t_weights
    return built, start


# --- readings of the first steps ---------------------------------------------


def host_batches(tokens: np.ndarray, steps: int, seq: int, rows: int) -> list:
    """The first steps' batches [rows, seq + 1] as the configuration
    states them (step i's sequence b starts at (i rows + b) seq,
    wrapping), cut from the stream on the host, not by the program."""
    out = []
    for i in range(steps):
        first = (i * rows + np.arange(rows)) * seq
        idx = first[:, None] + np.arange(seq + 1)[None, :]
        out.append(np.take(tokens, idx, mode="wrap").astype(np.int32))
    return out


def _leaf_norms(leaves: dict) -> dict:
    return {k: float(np.sqrt(np.sum(np.square(v, dtype=np.float64))))
            for k, v in leaves.items()}


def first_steps(built: Built, start, steps: int, b1: float) -> tuple:
    """Drive the stepper through its first ``steps`` steps.  Returns
    (state, readings): each loss; of the first step the per-pass
    cross-entropies, the mean exit probabilities, the gradient's norm
    and the clipped gradient as Adam got it (mu_1 = (1 - b1) g); the
    per-leaf norm of the parameters' change (``start`` None: not read)."""
    from hyperspace_tpu.models import looplm

    if built.steps_per_call != 1:
        raise Refused("the first steps are read one by one: a chunked "
                      "stepper (scan_chunk > 1) needs a driver of its own")
    state, got = built.state, {"losses": []}
    for i in range(steps):
        state, loss = built.stepper(state)
        got["losses"].append(float(loss))
        if i == 0:
            stats = looplm.read_stats(built.cfg, state.stats)
            mu = one._adam_mu(state.opt_state)
            if mu is None:
                raise Refused("no Adam first moment in the optimizer state")
            got.update(ce=stats["ce"], exit_prob=stats["exit_prob"],
                       grad_norm=stats["grad_norm"],
                       grads={k: v / np.float32(1.0 - b1)
                              for k, v in from_program_tree(mu).items()})
    if start is not None:
        end = from_program_tree(state.params)
        got["change_norms"] = _leaf_norms({k: end[k] - start[k]
                                           for k in end})
    return state, got


def first_step_of_twin(config: dict, traffic: dict, seed: int,
                       data_root: str):
    """The twin's readings of its first step (None: no twin stated),
    its state freed again."""
    twin_cfg = twin_config(config)
    if twin_cfg is None:
        return None
    built, _ = seeded_build(twin_cfg, traffic, seed, data_root)
    _, got = first_steps(built, None, 1,
                         float(config["recipe"]["adam_b1"]))
    del built
    gc.collect()
    return got


# --- the comparison -----------------------------------------------------------


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def gaps(got: dict, want: dict) -> dict:
    """Every number the cell's limits may name.  A pass one side lacks
    reads NaN, which no limit admits."""
    out = {}
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"]), 1):
        out[f"loss_gap_step{i}"] = _rel(a, b)
    passes = max(len(got["ce"]), len(want["ce"]))
    pad = lambda xs: list(xs) + [float("nan")] * (passes - len(xs))
    for t, (a, b) in enumerate(zip(pad(got["ce"]), pad(want["ce"])), 1):
        out[f"ce_gap_pass{t}"] = _rel(a, b)
    for t, (a, b) in enumerate(zip(pad(got["exit_prob"]),
                                   pad(want["exit_prob"])), 1):
        out[f"exit_prob_gap_pass{t}"] = abs(a - b)
    out["grad_norm_gap"] = _rel(got["grad_norm"], want["grad_norm"])
    # each side's gradient as its own clip left it
    clipped = {k: np.asarray(v) * np.float32(
        min(1.0, want["clip_norm"] / want["grad_norm"]))
        for k, v in want["grads"].items()}
    out["grad_difference"] = correctness.worst_leaf_difference(
        got["grads"], clipped)
    if "change_norms" in got:   # the twin's one step reads none
        moved = correctness.moved_leaves(_leaf_norms(clipped))
        out["change_norm_gap"] = correctness.worst_leaf_gap(
            got["change_norms"], want["change_norms"], moved)
    return out


def twin_config(config: dict):
    """The configuration with its ``check_twin`` laid over it (group by
    group), or None."""
    twin = config.get("check_twin")
    if not twin:
        return None
    out = dict(config, check_twin=None)
    for group in ("job", "recipe"):
        out[group] = {**config[group], **twin.get(group, {})}
    return out


def twin_gaps(got: dict, want: dict, found=None) -> dict:
    """The twin's first step against the same reference (``found``: its
    :func:`gaps` where the caller has them already).  At the stated
    bf16 lane the program and the reference differ by the rounding of
    every matmul operand, which is as much as a residual stream, norms
    and softmax in bf16 add to it; at float32 lanes the program sits
    within rounding of the reference and anything computed a step of
    precision down stands far clear (PERF.md gives both readings)."""
    found = gaps(got, want) if found is None else found
    passes = range(1, len(want["ce"]) + 1)
    return {"twin_loss_gap": found["loss_gap_step1"],
            "twin_ce_gap": max(found[f"ce_gap_pass{t}"] for t in passes),
            "twin_exit_prob_gap": max(found[f"exit_prob_gap_pass{t}"]
                                      for t in passes),
            "twin_grad_norm_gap": found["grad_norm_gap"],
            "twin_grad_difference": found["grad_difference"]}


def compare(got: dict, want: dict, limits: dict, got_twin=None) -> list:
    """[(name, value, limit)] for every number the cell's limits name;
    ``got_twin``: the twin's readings of its first step."""
    found = gaps(got, want)
    if got_twin is not None:
        found.update(twin_gaps(got_twin, want))
    return [(name, found[name], float(limit))
            for name, limit in limits.items()]


def follow_reference(config: dict, batches: list, seed: int, *,
                     blocked: bool, **kw) -> dict:
    """The plain reference's readings of the same steps from the same
    seed (``kw``: the control's dtype, a planted fault)."""
    model, opt = reference_model(config)
    want = ref.train_steps(
        make_weights(seed, config), batches, model, opt, blocked=blocked,
        remake=lambda: make_weights(seed, config), **kw)
    want["clip_norm"] = opt["clip_norm"]
    return want


# --- one run -------------------------------------------------------------


def cell_facts(config: dict) -> dict:
    model, job = model_of(config), config["job"]
    seq, rows = int(job["sequence_length"]), int(job["sequences_per_step"])
    return {"step_flops": costs_lm.step_flops(model, seq * rows, seq),
            "heads": int(model["num_attention_heads"]),
            "head_dim": int(model["head_dim"]), "seq": seq,
            "itemsize": 2 if job["precision"] == "bf16" else 4}


def run(ctx) -> dict:
    import importlib.util

    from hyperspace_tpu import compile_cache

    if importlib.util.find_spec("hyperspace_tpu.models.looplm") is None:
        # a checkout from before the trainer: say so at once, run nothing
        raise Refused("the program has no looplm trainer "
                      "(hyperspace_tpu/models/looplm.py)")
    guards.require_pallas()
    config, traffic = ctx.config, ctx.traffic
    if ctx.chips != 1:
        raise Refused("train_lm steps one chip")
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

    facts = dict(cell_facts(config), steps_traced=traced, chips=ctx.chips)
    tokens_per_step = built.data["tokens_per_step"]
    batches = host_batches(built.tokens, check_steps,
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
            "step_s_warm": step_s}
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
