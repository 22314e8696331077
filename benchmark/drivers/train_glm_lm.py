"""Driver ``train_glm_lm``: language-model pretraining steps of the MoE LM
(``hyperspace_tpu.models.moe_lm``) in GLM-4.7-Flash's form — latent
attention, sigmoid routing, a multi-token-prediction module — stepped by
``hyperspace_tpu.train.loop.run_loop``.

The wiring is ``train_moe_lm``'s, used as it is (:func:`build` is
``cli.train.run_moe_lm`` call for call); what differs is the weights'
names (the MTP module's), the readings (the main and the MTP losses
apart, out of the state's stats vector), the plain reference
(``benchmark/reference/glm47_flash.py``) and the cost count
(``benchmark/costs_mla.py``).  :func:`run` follows ``train_moe_lm``'s
order: refuse a program without latent attention at once, seeded
weights, the first ``check_steps`` steps read, the warm loop, the timed
window (its last seconds traced), the device memory, the float32 twin's
first step, the reference in blocks, the comparison.

A sample is one token with a main loss term: ``samples_per_s_chip`` =
tokens a step x optimizer steps completed / the window's seconds /
chips (the MTP module's 4,095 positions a step are work, not samples).
"""

from __future__ import annotations

import gc
import math
import os
import time

import numpy as np

from benchmark import costs_mla, guards
from benchmark.drivers import train_fullgraph as one
from benchmark.drivers import train_lm as lm
from benchmark.drivers import train_moe_lm as moe
from benchmark.guards import Refused
from benchmark.reference import glm47_flash as ref

MTP_PREFIX = "mtp."
MTP_TOP = ("enorm", "hnorm", "w_eh")


# --- weights: the reference's names <-> the program's tree -------------------


def to_program_tree(weights: dict, cfg) -> dict:
    """The harness's weights in the program's shape: the stack's by
    ``train_moe_lm``'s rule, the MTP module's under ``mtp`` (its layer's
    leaves stacked [1, ...])."""
    import jax.numpy as jnp

    tree = moe.to_program_tree({k: v for k, v in weights.items()
                                if not k.startswith(MTP_PREFIX)}, cfg)
    mtp = {k[len(MTP_PREFIX):]: v for k, v in weights.items()
           if k.startswith(MTP_PREFIX)}
    layer = {k[2:]: jnp.array(v)[None] for k, v in mtp.items()
             if k.startswith("l.")}
    if set(mtp) - {"l." + k for k in layer} != set(MTP_TOP) or not layer:
        raise Refused(f"the MTP module's weights {sorted(mtp)} are not the "
                      "configuration's")
    tree["mtp"] = {**{k: jnp.array(mtp[k]) for k in MTP_TOP},
                   "layer": layer}
    return tree


def from_program_tree(tree, cfg) -> dict:
    """{reference name: host array} of a tree in the program's shape."""
    out = moe.from_program_tree({k: v for k, v in tree.items()
                                 if k != "mtp"}, cfg)
    out.update({MTP_PREFIX + k: np.asarray(tree["mtp"][k]) for k in MTP_TOP})
    out.update({f"{MTP_PREFIX}l.{k}": np.asarray(v)[0]
                for k, v in tree["mtp"]["layer"].items()})
    return out


def make_weights(seed: int, config: dict) -> dict:
    return ref.init_weights(int(seed), lm.model_of(config), config["job"],
                            std=float(config["recipe"]["init_std"]))


def reference_opt(config: dict) -> dict:
    return dict(moe.reference_opt(config),
                mtp_weight=float(config["recipe"]["mtp_loss_weight"]))


def seeded_build(config: dict, traffic: dict, seed: int, data_root: str):
    """(built, host copy of the start): the program wired for the cell
    with the harness's weights from ``seed`` in its state."""
    import jax

    t0 = time.perf_counter()
    weights = make_weights(seed, config)
    start = {k: np.asarray(v) for k, v in weights.items()}
    tree = to_program_tree(weights, moe.program_config(config))
    del weights
    jax.block_until_ready(tree)
    t_weights = time.perf_counter() - t0
    built = moe.build(config, traffic, seed, data_root, params=tree)
    built.marks["setup_weights_s"] = t_weights
    return built, start


# --- readings of the first steps ---------------------------------------------


def first_steps(built: lm.Built, start, steps: int, b1: float) -> tuple:
    """Drive the stepper through its first ``steps`` steps.  Returns
    (state, readings): each step's main and MTP losses (the state's stats
    vector); of the first step the gradient's norm, the rows routed to
    held experts a sparse layer and the clipped gradient as Adam got it
    (mu_1 = (1 - b1) g); the per-leaf norm of the parameters' change
    (``start`` None: not read)."""
    from hyperspace_tpu.models import moe_lm

    if built.steps_per_call != 1:
        raise Refused("the first steps are read one by one: a chunked "
                      "stepper (scan_chunk > 1) needs a driver of its own")
    state, got = built.state, {"losses": [], "mtp_losses": []}
    for i in range(steps):
        state, _ = built.stepper(state)
        stats = moe_lm.read_stats(built.cfg, state.stats)
        got["losses"].append(stats["main_loss"])
        got["mtp_losses"].append(stats["mtp_loss"])
        if i == 0:
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
    """Every number the cell's limits may name: ``train_moe_lm``'s (the
    main loss's steps, the gradient, the change, the held rows of every
    sparse layer, the MTP layer's among them) and the MTP loss's steps."""
    out = moe.gaps(got, want)
    out.update({f"mtp_loss_gap_step{i}": lm._rel(a, b) for i, (a, b) in
                enumerate(zip(got["mtp_losses"], want["mtp_losses"]), 1)})
    return out


def twin_gaps(got: dict, want: dict) -> dict:
    """The twin's first step against the same reference: at float32 lanes
    the program sits within rounding of the reference, and anything
    computed a step of precision down stands clear (PERF.md)."""
    found = gaps(got, want)
    return {"twin_loss_gap": found["loss_gap_step1"],
            "twin_mtp_loss_gap": found["mtp_loss_gap_step1"],
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
    sparse layer, the MTP layer's last; the expected count where None),
    the flash calls' shapes, the rows routed to held experts, and what
    one grouped-matmul call (a sparse layer, one sequence) has to do on
    average: its rows and the held experts that got any
    (``held_experts``, each sparse layer's; all held where None)."""
    model, job = lm.model_of(config), config["job"]
    seq, rows = int(job["sequence_length"]), int(job["sequences_per_step"])
    sparse = costs_mla.sparse_layers(model)
    if held_rows is None:
        held_rows = [rows * costs_mla.expected_rows(model, job, seq)] * sparse
    if held_experts is None:
        held_experts = [rows * float(model["n_routed_experts"])] * sparse
    calls = max(sparse, 1) * rows
    return {"step_flops": costs_mla.step_flops(model, job, seq, rows,
                                               held_rows),
            "heads": int(model["num_attention_heads"]), "seq": seq,
            "head_dim": int(model["qk_nope_head_dim"])
            + int(model["qk_rope_head_dim"]),
            "itemsize": 2 if job["precision"] == "bf16" else 4,
            "hidden": int(model["hidden_size"]),
            "moe_width": int(model["moe_intermediate_size"]),
            "held_rows_sum": float(sum(held_rows)),
            "held_rows_mean": float(sum(held_rows)) / calls,
            "held_experts_mean": float(sum(held_experts)) / calls}


def run(ctx) -> dict:
    from hyperspace_tpu import compile_cache
    from hyperspace_tpu.models import moe_lm

    if "latent_attention" not in getattr(moe_lm, "LAYER_TYPES", ()):
        # a program from before latent attention: say so at once
        raise Refused("the program's moe_lm has no latent attention "
                      "(layer_types latent_attention)")
    guards.require_pallas()
    config, traffic = ctx.config, ctx.traffic
    if ctx.chips != 1:
        raise Refused("train_glm_lm steps one chip")
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
                 steps_traced=traced,
                 chips=ctx.chips)
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
            "held_rows_last": sum(last_rows),
            **{f"loss_step{i}": v for i, v in enumerate(got["losses"], 1)},
            **{f"mtp_loss_step{i}": v
               for i, v in enumerate(got["mtp_losses"], 1)}}
    checks = compare(got, want, ctx.limits, got_twin)
    checks.append(("compiles_in_window", compiled_in_window, 0.0))
    # the readings no limit holds (the limits file says why), for the record
    info.update({k: v for k, v in gaps(got, want).items()
                 if k not in ctx.limits})
    return {
        "attempted": total, "failed": 0,
        "end_to_end": {
            "samples_per_s_chip": tokens_per_step * total / seconds
            / ctx.chips,
            "setup_s": setup_s},
        "memory_peak_bytes": peak, "memory": mem_info, "facts": facts,
        "trace": red, "checks": checks, "info": info,
    }
