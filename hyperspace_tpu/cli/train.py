"""Single training entry point: ``python -m hyperspace_tpu.cli.train``.

SURVEY.md §5 "Config/flag system": typed dataclass configs, one per
workload (the five BASELINE.json configs), overridable from YAML and
``key=value`` CLI args; a config fully determines mesh, model, data and
optimizer — no hidden globals.

    python -m hyperspace_tpu.cli.train poincare steps=500 dim=10
    python -m hyperspace_tpu.cli.train hgcn task=lp dataset=cora
    python -m hyperspace_tpu.cli.train hybonet --yaml exp.yaml
    python -m hyperspace_tpu.cli.train hvae steps=200
    python -m hyperspace_tpu.cli.train product multihost=true
    python -m hyperspace_tpu.cli.train looplm --yaml configs/looplm_ouro_2p6b.yaml
    python -m hyperspace_tpu.cli.train moe_lm --yaml configs/moe_lm_laguna_s21.yaml
    python -m hyperspace_tpu.cli.train moe_lm --yaml configs/moe_lm_glm47_flash.yaml

Each run writes JSONL metrics (``--log``), optional orbax checkpoints
(``--ckpt-dir``), and prints one final JSON line of results.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


# --- run-level options (shared across workloads) ------------------------------


@dataclasses.dataclass
class RunConfig:
    steps: int = 500
    seed: int = 0
    eval_every: int = 0  # 0 = eval only at the end
    log: str | None = None  # JSONL path
    tensorboard_dir: str | None = None  # optional TB sink (process 0 only)
    ckpt_dir: str | None = None
    ckpt_every: int = 100
    resume: bool = False
    data_root: str | None = None  # on-disk dataset directory
    multihost: bool = False  # jax.distributed.initialize + host mesh axis
    # `model` extent of HGCN's auto mesh (1 = pure dp); the node-sharded
    # step cuts a node shard for every device whatever the split
    tp: int = 2
    # >1: run this many steps per dispatch as one lax.scan program
    # (train/loop.make_chunked_stepper; ALL workloads) — removes the
    # per-step launch latency that pins small-step workloads at the
    # dispatch floor (docs/benchmarks.md "chunked dispatch"); the step
    # budget rounds UP to a chunk multiple, and checkpoints/logs land on
    # chunk boundaries
    scan_chunk: int = 1
    # persistent on-disk graph-prep cache (data/prep_cache.py):
    # auto = cache big graphs only; true/false force on/off
    graph_cache: str = "auto"
    # >1: accumulate this many microbatch gradients per optimizer update
    # (hybonet/hvae; optax.MultiSteps — `steps` counts microsteps)
    accum: int = 1
    # mixed-precision policy preset (hyperspace_tpu/precision.py,
    # docs/precision.md): "f32" (default, bit-identical to a pre-policy
    # build) or "bf16" (compute in bf16; params, manifold boundary math
    # and reductions stay f32).  Copied into the workload config's own
    # `precision` field unless that is overridden explicitly.
    precision: str = "f32"
    # --- beyond-HBM host-resident table (poincare; docs/serving.md
    # "Beyond-HBM tables", train/host_embed.py) ------------------------
    # host_table=1: keep the packed embedding table (+ optimizer
    # moments) in HOST memory and train through a device hot-row cache
    # — per-chunk unique-id gather, one planned-sparse dispatch per
    # chunk, write-back at each chunk boundary.  Bitwise-identical to
    # the in-HBM planned-packed trainer on tables that fit (tested).
    host_table: bool = False
    # device hot-row cache capacity in rows (0 = the chunk's worst-case
    # working set, capped at the table)
    hot_rows: int = 0
    # planned steps per host chunk (one device dispatch each)
    host_chunk_steps: int = 8
    # overlap upcoming chunks' master-row gathers with the current
    # chunk's device work: an evicted-and-retouched row may be read up
    # to prefetch_depth+1 = 3 chunks stale (the prefetcher runs that
    # far ahead of the write-back; bounded-staleness trade — the
    # default synchronous gather keeps the bitwise contract)
    host_gather_ahead: bool = False
    # persistent XLA compilation cache (hyperspace_tpu/compile_cache.py,
    # docs/observability.md "Compilation cache"): default ON — where
    # JAX_COMPILATION_CACHE_DIR says if that is set (no other directory
    # is then accepted), else at <repo>/.cache/jax_compile unless this
    # flag or HYPERSPACE_COMPILE_CACHE names a path; 0 disables.  Run #2
    # of the same program shapes deserializes executables instead of
    # re-invoking XLA (`jax/compile_cache_hit` counts them).
    compile_cache_dir: str | None = None
    # --- telemetry (docs/observability.md) -----------------------------
    # telemetry=1: run manifest as the FIRST JSONL record, span/* host
    # timings + ctr/* counter snapshots in every log record, and a final
    # telemetry_summary record.  Off (default) adds no per-step host
    # sync and no extra dispatches.
    telemetry: bool = False
    # write a Chrome/Perfetto trace_events JSON of the host spans here
    # (implies span recording even without telemetry=1)
    trace_out: str | None = None
    # write the counter registry as a Prometheus text-format snapshot
    # here every metrics_every= seconds (telemetry/exposition.py,
    # docs/observability.md "Live metrics"): atomic write-then-rename,
    # so a node exporter's textfile collector makes the training job
    # scrapeable with no port open.  Off (default) constructs nothing.
    metrics_out: str | None = None
    metrics_every: float = 30.0
    # >0: per-step phase decomposition for the first N dispatches
    # (train/telemetry.py): block_until_ready at the phase boundary so
    # data_wait / host_gather / device_step / write_back histograms
    # read real durations (the sync costs pipelining — bounded to the
    # profile window), plus jax.profiler trace annotations and the
    # compile-event hook.  0 (default) = free-running.
    profile_steps: int = 0
    # >0: sample the on-device numerical-health stats every N chunks
    # (telemetry/health.py): ball boundary margin, hyperboloid
    # constraint residual, nonfinite counts — logged as health/* records
    # and threshold-checked (warn; health_abort=1 raises instead)
    health_every: int = 0
    health_eps: float = 1e-2  # warn when boundary margin drops below
    health_tol: float = 1e-3  # warn when constraint violation exceeds
    health_abort: bool = False
    # --- resilience (docs/resilience.md) -------------------------------
    # chaos=site:kind[:key=value...][,...] arms the seeded fault
    # registry (resilience/faults.py) — e.g.
    # chaos=train.step_nan:nan:after=2 poisons one chunk; chaos_seed=
    # seeds probabilistic specs.  Off (default) every site is one
    # module-bool read.
    chaos: str | None = None
    chaos_seed: int = 0
    # rollback=N: divergence guard — on non-finite loss or a health
    # violation, rewind to the last COMMITTED checkpoint (needs
    # ckpt_dir=), re-seed stream-fed data past the poisoned chunk, and
    # record the incident; after N rollbacks the run fails loudly.
    # 0 (default) keeps warn/abort.  lr_backoff^attempt is computed,
    # recorded, and handed to the on_rollback hook — steppers that can
    # rebuild their optimizer apply it there (the built-in runners
    # currently re-seed only; docs/resilience.md).
    rollback: int = 0
    rollback_lr_backoff: float = 0.5
    coordinator: str = "127.0.0.1:9357"
    num_processes: int = 1
    process_id: int = 0


def _coerce(old: Any, s: str) -> Any:
    if old is None:
        return s
    t = type(old)
    if t is bool:
        return s.lower() in ("1", "true", "yes")
    if dataclasses.is_dataclass(old):
        raise ValueError("cannot override nested config directly")
    if t is tuple:
        return tuple(json.loads(s))
    try:
        return t(s)
    except (TypeError, ValueError):
        return s


def apply_overrides(cfg, overrides: dict[str, str]):
    """Apply {field: str} overrides to a (frozen) dataclass, coercing types."""
    updates = {}
    names = {f.name: f for f in dataclasses.fields(cfg)}
    for k, v in overrides.items():
        if k not in names:
            raise SystemExit(
                f"unknown option {k!r} for {type(cfg).__name__}; "
                f"known: {sorted(names)}")
        updates[k] = _coerce(getattr(cfg, k), v)
    return dataclasses.replace(cfg, **updates)


def split_overrides(pairs: list[str], run: RunConfig):
    """Partition key=value args into (run-config updates, workload updates)."""
    run_names = {f.name for f in dataclasses.fields(RunConfig)}
    run_kv, wl_kv = {}, {}
    for p in pairs:
        if "=" not in p:
            raise SystemExit(f"expected key=value, got {p!r}")
        k, v = p.split("=", 1)
        (run_kv if k in run_names else wl_kv)[k] = v
    return apply_overrides(run, run_kv), wl_kv


# --- workload runners ---------------------------------------------------------


def _maybe_accum(run: RunConfig, opt, state):
    """Wrap ``opt`` for gradient accumulation when ``run.accum > 1``.

    Rebuilds the optimizer state (a wrapped transform has a different
    state pytree — the old one must never be reused)."""
    if run.accum <= 1:
        return opt, state
    from hyperspace_tpu.optim.accum import with_grad_accumulation

    opt, opt_state = with_grad_accumulation(opt, state.params, run.accum)
    return opt, state._replace(opt_state=opt_state)


def _reject_accum(run: RunConfig, workload: str):
    if run.accum > 1:
        raise SystemExit(
            f"accum>1 is wired for hybonet/hvae only — the {workload} "
            "step updates full-batch (hgcn full-graph) or sparse rows "
            "(embeddings), where microbatch accumulation has no meaning")


def _graph_cache(run: RunConfig):
    """RunConfig.graph_cache → the data.graphs ``cache`` argument."""
    v = run.graph_cache.lower()
    if v in ("1", "true", "yes", "on"):
        return True
    if v in ("0", "false", "no", "off"):
        return False
    if v == "auto":
        return "auto"
    raise SystemExit(f"graph_cache={run.graph_cache!r}: want auto/true/false")


def _chunk_run(run: RunConfig) -> RunConfig:
    """Round the step budget up to a scan_chunk multiple — every dispatch
    runs exactly one full chunk, so checkpoint/log step numbers always
    equal the steps actually taken."""
    from hyperspace_tpu.train import loop

    rounded = loop.round_steps_to_chunk(run.steps, run.scan_chunk)
    if rounded != run.steps:
        print(f"scan_chunk={run.scan_chunk}: step budget rounded up "
              f"{run.steps} -> {rounded} (every dispatch runs a full "
              "chunk)", flush=True)
    return dataclasses.replace(run, steps=rounded)


def _chunked(run: RunConfig, step_fn):
    """(stepper, steps_per_call): ``step_fn`` wrapped for chunked
    dispatch when ``run.scan_chunk > 1`` (one lax.scan program per
    ``scan_chunk`` steps, state donated), unchanged otherwise.  The
    run's precision policy rides into the chunk program (its arg-cast
    hook is a no-op for the CLI's closure-style steppers, but keeps the
    contract uniform for library callers — train/loop.py)."""
    k = max(int(run.scan_chunk), 1)
    if k <= 1:
        return step_fn, 1
    from hyperspace_tpu.train import loop

    return loop.make_chunked_stepper(step_fn, k, policy=run.precision), k


def _precision_default(run: RunConfig, overrides: dict) -> dict:
    """Copy the run-level ``precision=`` into the workload config unless
    the workload override set it explicitly (explicit wins)."""
    overrides.setdefault("precision", run.precision)
    return overrides


def run_poincare(run: RunConfig, overrides: dict):
    _reject_accum(run, "poincare")
    from hyperspace_tpu.models import poincare_embed as pe

    ds, data = _closure_dataset(run, branching=4)
    cfg = apply_overrides(
        pe.PoincareEmbedConfig(num_nodes=ds.num_nodes),
        _precision_default(run, overrides))
    state, opt = pe.init_state(cfg, run.seed)
    pairs = jnp.asarray(ds.pairs)
    from hyperspace_tpu.manifolds import PoincareBall

    ball = PoincareBall(cfg.c)
    project = lambda st: st._replace(table=ball.proj(st.table))
    if run.host_table:
        # beyond-HBM path (train/host_embed.py): host master + device
        # hot-row cache, one planned-sparse dispatch per chunk
        from hyperspace_tpu.train import host_embed as he

        if cfg.sparse or run.scan_chunk > 1:
            raise SystemExit(
                "host_table=1 IS the planned-sparse chunked path — drop "
                "sparse=true / scan_chunk (chunking is host_chunk_steps=)")
        trainer = he.HostPlannedTrainer.from_state(
            cfg, opt, state, chunk_steps=run.host_chunk_steps,
            hot_rows=run.hot_rows, seed=run.seed,
            gather_ahead=run.host_gather_ahead,
            profile=bool(getattr(run, "profile_steps", 0)))
        trainer.run(ds.pairs, run.steps)
        if run.ckpt_dir:
            from hyperspace_tpu.parallel import multihost as mh

            d = os.path.join(run.ckpt_dir, "host_table")
            if jax.process_count() > 1:
                # pod save: each process writes ONLY its owned row range,
                # process 0 commits the manifest behind a barrier — same
                # on-disk layout, restorable at any process count
                # (parallel/host_table.save_owned_rows)
                from hyperspace_tpu.parallel import host_table as HT

                HT.save_owned_rows(trainer.master, d,
                                   barrier=lambda: mh.sync("host_table"))
            else:
                # sharded master save: one bounded block per shard, never
                # the full table in one array (parallel/host_table.py)
                trainer.master.save_sharded(d)
        if cfg.num_nodes > he.EVAL_MAX_ROWS:
            # materializing the table for eval would defeat the
            # beyond-HBM design at exactly the scale it exists for —
            # the sharded master (+ the serve lanes) is the product
            return {"workload": "poincare", "steps": int(trainer.step),
                    **data, "host_table": True,
                    "eval_skipped": "beyond-hbm"}
        state = project(trainer.to_state())
        with _eval_span():
            res = pe.evaluate(state.table, ds.pairs, cfg.c)
        return {"workload": "poincare", "steps": int(state.step),
                **data, "host_table": True, **res}
    if run.scan_chunk > 1 and cfg.sparse:
        raise SystemExit(
            "scan_chunk>1 scans the dense step body only — drop "
            "sparse=true or scan_chunk (the planned-sparse scan lives "
            "in poincare_embed.train_epoch_planned_packed)")
    if run.scan_chunk > 1:
        run = _chunk_run(run)
    step_fn = pe.make_train_step(cfg)
    stepper, spc = _chunked(run, lambda st: step_fn(cfg, opt, st, pairs))
    health_fn = _maybe_health(run, lambda: _make_health(
        ball, params_of=lambda st: st.table))
    state, loss = _train_loop(run, state, stepper, project=project,
                              steps_per_call=spc, health_fn=health_fn,
                              data=data)
    with _eval_span():
        res = pe.evaluate(state.table, ds.pairs, cfg.c)
    # state.step is the authoritative count (survives resume/chunk
    # rounding — a resumed chunked run can legitimately exceed run.steps)
    return {"workload": "poincare", "steps": int(state.step), **data,
            "loss": float(loss), **res}


def _resume_chunk(run: RunConfig, chunk_steps: int) -> int:
    """Starting chunk index for a SampledBatchStream — ceil(R/cs), see
    :func:`hyperspace_tpu.train.loop.resume_chunk` (the ONE home of the
    ceil-not-floor rationale, ADVICE r04)."""
    from hyperspace_tpu.train import loop

    return loop.resume_chunk(run.ckpt_dir, run.resume, chunk_steps)


def _sampled_chunk_steps(run: RunConfig, plan_steps: int) -> int:
    """Stream chunk size for the sampled trainers: ``plan_steps`` caps
    the device-resident pyramid footprint, the step budget caps it from
    above; with chunked dispatch the scan must divide the stream chunk so
    every pull lands on a chunk boundary."""
    cs = min(run.steps, plan_steps)
    if run.scan_chunk > 1 and (run.scan_chunk > cs or cs % run.scan_chunk):
        # never silently exceed the plan_steps footprint cap: a scan
        # bigger than the stream chunk would force bigger host batches
        # onto the device, which is exactly what plan_steps bounds
        raise SystemExit(
            f"scan_chunk={run.scan_chunk} must divide the sampled "
            f"stream's chunk size {cs} (= min(steps, plan_steps)) — "
            "raise plan_steps to a multiple of scan_chunk or lower "
            "scan_chunk")
    return cs


def _stream_stepper(stream, step_fn, steps_per_call: int = 1):
    """Stepper that pulls a fresh pyramid chunk every ``chunk_steps``
    DEVICE steps from a :class:`hgcn_sampled.SampledBatchStream` — long
    runs never recycle batches (VERDICT r3 #5).  ``step_fn(state,
    batches)`` may itself run ``steps_per_call`` steps per call (the
    chunked-dispatch wrapper); the caller guarantees ``chunk_steps %
    steps_per_call == 0`` so pulls stay on stream-chunk boundaries.  The
    device step indexes its pyramid row by ``state.step % chunk_steps``;
    a resume offset only rotates the within-chunk consumption order
    (batches are iid draws), every row of every chunk is still consumed
    exactly once.  The CHUNK sequence itself continues across restarts
    via ``_resume_chunk``."""
    holder = {"batches": None, "done": 0}

    def stepper(st):
        if holder["done"] % stream.chunk_steps == 0:
            holder["batches"] = stream.next()
        holder["done"] += steps_per_call
        return step_fn(st, holder["batches"])

    def on_rollback(restored_step, attempt, lr_scale):
        # divergence rollback (docs/resilience.md): drop the resident
        # chunk and realign to a chunk boundary so the NEXT call pulls
        # a FRESH stream chunk — batches are iid draws, so the poisoned
        # chunk is skipped, never replayed (replaying it would diverge
        # identically)
        holder["batches"] = None
        holder["done"] = 0

    # picked up by run_loop via the runner (`on_rollback=` kwarg)
    stepper.on_rollback = on_rollback
    return stepper


def hgcn_mode_defaults(base, overrides: dict, sampled: bool):
    """Mode-aware HGCN defaults (VERDICT r3 #2).

    The full-graph lr=1e-2 is measured-bad for two modes
    (docs/benchmarks.md): sampled minibatch gradients oscillate at 1e-2
    (val acc 0.3–0.76 swings) and the attention arm collapses 2-of-3
    seeds to the degenerate logits-0 solution.  3e-3 reaches the plateau
    in both studies; attention additionally gets grad-norm clipping
    (the collapse is driven by early gradient spikes).  Explicit lr= /
    clip_norm= overrides always win.  NOTE: a run resumed from a
    checkpoint re-derives its lr from config, so a pre-r4 sampled /
    attention checkpoint resumes at the NEW default lr unless the old
    value is passed explicitly.
    """
    use_att = _coerce(False, overrides.get("use_att", "false"))
    if (sampled or use_att) and "lr" not in overrides:
        base = dataclasses.replace(base, lr=3e-3)
    if use_att and "clip_norm" not in overrides:
        base = dataclasses.replace(base, clip_norm=1.0)
    return base


def run_hgcn(run: RunConfig, overrides: dict):
    _reject_accum(run, "hgcn")
    if run.scan_chunk > 1:
        run = _chunk_run(run)
    gc = _graph_cache(run)
    from hyperspace_tpu.data import graphs as G
    from hyperspace_tpu.models import hgcn

    task = overrides.pop("task", "lp")
    dataset = overrides.pop("dataset", "cora")
    # reorder=true|bfs → BFS locality order; reorder=community → LPA
    # community order (best block density on community graphs)
    reorder = overrides.pop("reorder", "false").lower()
    # neighbor-sampled minibatch mode (task=nc or lp): fixed-fanout
    # pyramids from the native sampler; supervises `batch` seeds/step
    sampled = overrides.pop("sampled", "false").lower() in ("1", "true", "yes")
    fanouts = tuple(json.loads(overrides.pop("fanouts", "[10, 10]")))
    batch = int(overrides.pop("batch", "512"))
    # batches are pre-planned host-side and recycled modulo this count —
    # caps the [S, B, f1, f2] id pyramid's device footprint on long runs
    plan_steps = int(overrides.pop("plan_steps", "64"))
    edges, x, labels, ncls, source = G.load_graph(dataset, run.data_root)
    # which data ran, at what size: into the manifest AND the result — a
    # synthetic stand-in for a named dataset must be visible in both
    data = {"dataset": dataset, "source": source,
            "num_nodes": int(x.shape[0]), "num_edges": int(len(edges)),
            "feat_dim": int(x.shape[1])}
    if reorder not in ("0", "false", "no", "1", "true", "yes", "bfs",
                       "community"):
        raise SystemExit(
            f"reorder={reorder!r}: want true/false, bfs, or community")
    if reorder in ("1", "true", "yes", "bfs", "community"):
        # locality relabeling: feeds the cluster-pair kernel
        edges, x, labels, _ = G.apply_locality_order(
            edges, x, labels,
            method="community" if reorder == "community" else "bfs",
            cache=gc)
    base = hgcn_mode_defaults(
        hgcn.HGCNConfig(feat_dim=x.shape[1],
                        num_classes=ncls if task == "nc" else 0),
        overrides, sampled)
    cfg = apply_overrides(base, _precision_default(run, overrides))
    num_nodes = x.shape[0]
    from hyperspace_tpu.parallel.mesh import auto_mesh

    mesh = auto_mesh(run.multihost, tp=run.tp)
    if task == "lp":
        split = G.split_edges(
            edges, num_nodes, x, seed=run.seed,
            cluster_min_pair=G.cluster_min_pair_for(cfg.use_att),
            cache=gc)
        if sampled:
            # minibatch LP (models/hgcn_sampled.py): pyramids over the
            # four endpoint chunks; full-graph eval on the shared tree
            if run.multihost:
                raise SystemExit(
                    "sampled=true is single-process — drop multihost=true")
            from hyperspace_tpu.models import hgcn_sampled as HS

            scfg = HS.SampledConfig(base=cfg, fanouts=fanouts,
                                    batch_size=batch)
            model_s, opt, state = HS.init_sampled_lp(
                scfg, feat_dim=x.shape[1], seed=run.seed)
            xt = jnp.asarray(np.asarray(x, np.float32))
            chunk_steps = _sampled_chunk_steps(run, plan_steps)
            with HS.SampledBatchStream(
                    scfg, "lp", num_nodes=num_nodes,
                    train_pos=split.train_pos,
                    chunk_steps=chunk_steps, seed=run.seed,
                    start_chunk=_resume_chunk(run, chunk_steps)) as stream:
                chunk_fn, spc = _chunked(
                    run, lambda st, b: HS.train_step_sampled_lp(
                        model_s, opt, st, xt, stream.deg, b))
                stepper = _stream_stepper(stream, chunk_fn,
                                          steps_per_call=spc)
                state, loss = _train_loop(
                    run, state, stepper, steps_per_call=spc,
                    health_fn=_maybe_health(run, _make_health), data=data)
            full = hgcn.HGCNLinkPred(cfg)
            with _eval_span():
                res = {"loss": float(loss), **hgcn.evaluate_lp(
                    full, state.params, split, "test")}
            return {"workload": "hgcn", "task": "lp", **data,
                    "steps": int(state.step), "sampled": True, **res}
        model, opt, state = hgcn.init_lp(cfg, split.graph, seed=run.seed)
        if mesh is not None:
            from hyperspace_tpu.parallel import multihost as mh

            # per-host data plane: every process computes the SAME padded
            # pair batch (round_up_pairs pads to a mesh multiple, so the
            # rows divide evenly), feeds only its own row range, and
            # distribute_batch assembles the global batch-sharded array —
            # host→device supervision traffic scales 1/n_processes
            # (single-process this is a plain sharded device_put)
            train_pos = mh.distribute_batch(
                _placed(hgcn.round_up_pairs(split.train_pos, mesh)), mesh)
            # default multi-chip path: node-sharded encoder — each device
            # owns N/ndev nodes and their incoming edges (mean AND
            # attention aggregation; the receiver partition keeps the
            # attention softmax shard-local)
            # the node-sharded graph serves the final evaluation too: one
            # device need not hold the whole graph, nor the parameters
            step, state, ga = hgcn.make_node_sharded_step_lp(
                model, opt, num_nodes, mesh, state, split)
            stepper, spc = _chunked(run, lambda st: step(st, ga, train_pos))
        else:
            ga = hgcn._device_graph(split.graph)
            train_pos = _placed(split.train_pos)
            stepper, spc = _chunked(
                run, lambda st: hgcn.train_step_lp(model, opt, num_nodes,
                                                   st, ga, train_pos))
        state, loss = _train_loop(run, state, stepper, steps_per_call=spc,
                                  health_fn=_maybe_health(run, _make_health),
                                  data=data)
        with _eval_span():
            res = {"loss": float(loss), **hgcn.evaluate_lp(
                model, state.params, split, "test", ga=ga)}
    else:
        tr, va, te = G.node_split_masks(num_nodes, seed=run.seed)
        g = G.prepare(edges, num_nodes, x, labels=labels, num_classes=ncls,
                      train_mask=tr, val_mask=va, test_mask=te,
                      cluster_min_pair=G.cluster_min_pair_for(cfg.use_att),
                      cache=gc)
        if sampled:
            # minibatch trainer (models/hgcn_sampled.py): single-device
            # dense-block steps (a local mesh is simply unused);
            # evaluation runs the FULL-GRAPH model on the sampled-trained
            # parameters (identical param tree)
            if run.multihost:
                raise SystemExit(
                    "sampled=true is single-process — drop multihost=true "
                    "(sampled minibatch DP is not wired yet)")
            from hyperspace_tpu.models import hgcn_sampled as HS

            scfg = HS.SampledConfig(base=cfg, fanouts=fanouts,
                                    batch_size=batch)
            model_s, opt, state = HS.init_sampled_nc(
                scfg, feat_dim=x.shape[1], seed=run.seed)
            xt = jnp.asarray(np.asarray(x, np.float32))
            chunk_steps = _sampled_chunk_steps(run, plan_steps)
            with HS.SampledBatchStream(
                    scfg, "nc", num_nodes=num_nodes, edges=edges,
                    labels=labels, train_mask=tr,
                    chunk_steps=chunk_steps, seed=run.seed,
                    start_chunk=_resume_chunk(run, chunk_steps)) as stream:
                chunk_fn, spc = _chunked(
                    run, lambda st, b: HS.train_step_sampled_nc(
                        model_s, opt, st, xt, stream.deg, b))
                stepper = _stream_stepper(stream, chunk_fn,
                                          steps_per_call=spc)
                state, loss = _train_loop(
                    run, state, stepper, steps_per_call=spc,
                    health_fn=_maybe_health(run, _make_health), data=data)
            full = hgcn.HGCNNodeClf(cfg)
            with _eval_span():
                res = {"loss": float(loss),
                       **hgcn.evaluate_nc(full, state.params, g)}
            return {"workload": "hgcn", "task": "nc", **data,
                    "steps": int(state.step), "sampled": True, **res}
        model, opt, state = hgcn.init_nc(cfg, g, seed=run.seed)
        ga = hgcn._device_graph(g)
        lab = jnp.asarray(g.labels)
        mask = jnp.asarray(g.train_mask)
        if mesh is not None:
            step, state, ga_s, lab_s, mask_s = (
                hgcn.make_node_sharded_step_nc(model, opt, mesh, state, g))
            stepper, spc = _chunked(
                run, lambda st: step(st, ga_s, lab_s, mask_s))
        else:
            stepper, spc = _chunked(
                run, lambda st: hgcn.train_step_nc(model, opt, st, ga, lab,
                                                   mask))
        state, loss = _train_loop(run, state, stepper, steps_per_call=spc,
                                  health_fn=_maybe_health(run, _make_health),
                                  data=data)
        with _eval_span():
            res = {"loss": float(loss), **hgcn.evaluate_nc(
                model, _eval_params(state.params, mesh), g, ga=ga)}
    return {"workload": "hgcn", "task": task, **data,
            "steps": int(state.step), **res}


def _eval_params(params, mesh):
    """Parameters for the full-graph evaluation.  That program runs on
    ONE device — its Pallas kernels sit outside any ``shard_map``, and
    the partitioner refuses to place a Mosaic kernel by itself — so
    parameters a mesh run left on its devices are gathered onto the
    device the evaluation graph goes to."""
    if mesh is None:
        return params
    return jax.device_put(params, jax.local_devices()[0])


def run_hybonet(run: RunConfig, overrides: dict):
    from hyperspace_tpu.data import text as T
    from hyperspace_tpu.models import hybonet

    dataset = overrides.pop("dataset", "text")
    ds, source = T.load_text(dataset, run.data_root)
    data = {"dataset": dataset, "source": source,
            "num_examples": int(ds.tokens.shape[0])}
    tr, te = ds.split(0.8, seed=run.seed)
    cfg = apply_overrides(
        hybonet.HyboNetConfig(vocab_size=ds.vocab_size,
                              num_classes=ds.num_classes,
                              max_len=ds.tokens.shape[1]),
        _precision_default(run, overrides))
    model, opt, state = hybonet.init_model(cfg, seed=run.seed)
    opt, state = _maybe_accum(run, opt, state)
    toks, mask, labels = (jnp.asarray(tr.tokens), jnp.asarray(tr.mask),
                          jnp.asarray(tr.labels))
    from hyperspace_tpu.parallel.mesh import auto_mesh

    mesh = auto_mesh(run.multihost)
    if run.scan_chunk > 1:
        run = _chunk_run(run)
    if mesh is not None:
        step, state, (toks, mask, labels) = hybonet.make_sharded_step(
            model, opt, mesh, state, toks, mask, labels)
        base = lambda st: step(st, toks, mask, labels)
    else:
        base = lambda st: hybonet.train_step_sampled(model, opt, st, toks,
                                                     mask, labels)
    stepper, spc = _chunked(run, base)
    state, loss = _train_loop(run, state, stepper, steps_per_call=spc,
                              health_fn=_maybe_health(run, _make_health),
                              data=data)
    with _eval_span():
        res = hybonet.evaluate(model, state.params, te)
    return {"workload": "hybonet", **data, "steps": int(state.step),
            "loss": float(loss), **res}


def run_hvae(run: RunConfig, overrides: dict):
    from hyperspace_tpu.data import mnist as M
    from hyperspace_tpu.models import hvae

    ds, source = M.load_mnist(run.data_root)
    data = {"source": source, "num_examples": int(ds.images.shape[0])}
    cfg = apply_overrides(hvae.HVAEConfig(image_size=ds.images.shape[1]),
                          _precision_default(run, overrides))
    model, opt, state = hvae.init_model(cfg, seed=run.seed)
    opt, state = _maybe_accum(run, opt, state)
    x_all = jnp.asarray(ds.images, cfg.dtype)
    metrics = {}
    from hyperspace_tpu.parallel.mesh import auto_mesh

    mesh = auto_mesh(run.multihost)
    if run.scan_chunk > 1:
        run = _chunk_run(run)
    if mesh is not None:
        step, state, x_all = hvae.make_sharded_step(model, opt, mesh, state,
                                                    x_all)
        fn = lambda st: step(st, x_all)
    else:
        fn = lambda st: hvae.train_step_sampled(model, opt, st, x_all)

    chunk_fn, spc = _chunked(run, fn)

    def stepper(st):
        if spc == 1:
            st, loss, recon, kl = chunk_fn(st)
        else:  # scanned chunk: per-step aux stacked [spc]; keep the last
            st, (loss, recon, kl) = chunk_fn(st)
            recon, kl = recon[-1], kl[-1]
        metrics["rk"] = (recon, kl)  # device arrays; fetched once at the end
        return st, loss

    state, loss = _train_loop(run, state, stepper, steps_per_call=spc,
                              health_fn=_maybe_health(run, _make_health),
                              data=data)
    recon, kl = (float(v) for v in metrics.get("rk", (jnp.nan,) * 2))
    loss = float(loss)
    x = jnp.asarray(ds.images[:256], cfg.dtype)
    with _eval_span():
        iwae = float(hvae.iwae_bound(model, state.params, x,
                                     jax.random.PRNGKey(1), k=16))
    return {"workload": "hvae", **data, "steps": int(state.step),
            "loss": loss, "recon": recon, "kl": kl, "iwae": iwae}


def run_product(run: RunConfig, overrides: dict):
    _reject_accum(run, "product")
    from hyperspace_tpu.models import product_embed as pme
    from hyperspace_tpu.parallel.mesh import auto_mesh

    ds, data = _closure_dataset(run, branching=3)
    cfg = apply_overrides(
        pme.ProductEmbedConfig(num_nodes=ds.num_nodes),
        _precision_default(run, overrides))
    state, curv_opt = pme.init_state(cfg, run.seed)
    pairs = jnp.asarray(ds.pairs)
    mesh = auto_mesh(run.multihost)
    if run.scan_chunk > 1:
        run = _chunk_run(run)
    if mesh is not None:
        step = pme.make_sharded_step(cfg, curv_opt, mesh)
        base = lambda st: step(st, pairs)
    else:
        base = lambda st: pme.train_step(cfg, curv_opt, state=st, pairs=pairs)
    stepper, spc = _chunked(run, base)
    def project(st):
        m = pme.build_manifold(cfg, st.params.c_raw)
        return st._replace(params=st.params._replace(
            table=m.proj(st.params.table)))

    def product_health():
        # the product manifold is rebuilt from the LEARNED curvatures
        # each check, so health reflects the geometry as trained
        from hyperspace_tpu.telemetry.health import health_stats

        def fn(st):
            m = pme.build_manifold(cfg, st.params.c_raw)
            return health_stats(st.params.table, m)

        return jax.jit(fn)

    state, loss = _train_loop(run, state, stepper, project=project,
                              steps_per_call=spc,
                              health_fn=_maybe_health(run, product_health),
                              data=data)
    with _eval_span():
        res = pme.evaluate(cfg, state.params, ds.pairs)
    return {"workload": "product", "steps": int(state.step), **data,
            "loss": float(loss), **res,
            "curvatures": pme.curvatures(cfg, state.params)}


# keys of a published Ouro ``config.json`` that this trainer holds fixed:
# any other value is a model this code does not run (as the CLI's yaml
# loader prints them)
_LOOPLM_FIXED = {"model_type": "ouro", "rope_scaling": "None",
                 "sliding_window": "None", "use_sliding_window": "False"}
# ... and keys that say nothing to a training step (inference's exit
# threshold, the window layers of a sliding window that is off)
_LOOPLM_UNUSED = ("max_window_layers", "early_exit_threshold")


def _looplm_config(run: RunConfig, overrides: dict):
    """(LoopLMConfig, stream arguments) from a published config's keys
    plus the job's."""
    from hyperspace_tpu.models import looplm

    overrides = dict(overrides)
    for key, want in _LOOPLM_FIXED.items():
        got = str(overrides.pop(key, want))
        if got != want:
            raise SystemExit(f"looplm: {key}={got!r} is not supported "
                             f"(this trainer runs {key}={want})")
    for key in _LOOPLM_UNUSED:
        overrides.pop(key, None)
    kinds = set(json.loads(overrides.pop("layer_types", "[]")))
    if kinds - {"full_attention"}:
        raise SystemExit(f"looplm: layer_types {sorted(kinds)}: only "
                         "full_attention layers are supported")
    max_pos = int(overrides.pop("max_position_embeddings", 0))
    stream_kw = {"num_tokens": int(overrides.pop("stream_tokens", 1 << 16))}
    try:
        cfg = apply_overrides(looplm.LoopLMConfig(),
                              _precision_default(run, overrides))
    except ValueError as e:  # LoopLMConfig's own checks
        raise SystemExit(f"looplm: {e}") from None
    if max_pos and cfg.sequence_length > max_pos:
        raise SystemExit(f"looplm: sequence_length={cfg.sequence_length} "
                         f"exceeds max_position_embeddings={max_pos}")
    return cfg, stream_kw


def _at_log_boundaries(stepper, spc: int, every: int, fn):
    """``stepper`` that calls ``fn(state)`` whenever a call crosses a log
    boundary (the cadence ``run_loop`` logs at; one small host fetch
    there)."""
    done = [0]

    def stepped(state):
        state, loss = stepper(state)
        prev, done[0] = done[0], done[0] + spc
        if done[0] // every > prev // every:
            fn(state)
        return state, loss

    return stepped


def _looplm_gauges(cfg, stepper, spc: int, every: int):
    """``stepper`` with the registry's looplm/* gauges set from the
    state's stats vector at each log boundary."""
    from hyperspace_tpu.models import looplm
    from hyperspace_tpu.telemetry import registry as telem

    t = cfg.total_ut_steps
    telem.set_gauge("looplm/ut_steps", t)
    telem.set_gauge("looplm/tokens_per_step",
                    cfg.sequence_length * cfg.sequences_per_step)

    def gauges(state):
        st = looplm.read_stats(cfg, state.stats)  # hyperlint: disable=host-sync-in-hot-path — once a log boundary, beside the loop's own loss fetch
        for i in range(t):
            telem.set_gauge(f"looplm/exit_prob_t{i + 1}",  # telemetry-catalog: looplm/exit_prob_t<t>
                            st["exit_prob"][i])
            telem.set_gauge(f"looplm/ce_t{i + 1}",  # telemetry-catalog: looplm/ce_t<t>
                            st["ce"][i])
        telem.set_gauge("looplm/expected_exit_step", sum(
            (i + 1) * p for i, p in enumerate(st["exit_prob"])))

    return _at_log_boundaries(stepper, spc, every, gauges)


def _run_token_lm(run: RunConfig, overrides: dict, name: str, model,
                  make_config, add_gauges):
    """A token language model (``looplm``, ``moe_lm``) trained from a
    published config's keys plus the job's: the packed token stream, the
    model's ``init_state`` and jitted ``train_step`` under ``run_loop``,
    gauges from ``add_gauges``.  Returns (cfg, state, last loss, data
    record)."""
    _reject_accum(run, name)
    from hyperspace_tpu.data import text as T

    cfg, stream_kw = make_config(run, overrides)
    tokens, source = T.load_token_stream(
        run.data_root, vocab_size=cfg.vocab_size, **stream_kw)
    need = cfg.sequence_length * cfg.sequences_per_step + 1
    if tokens.size < need or int(tokens.max()) >= cfg.vocab_size:
        raise SystemExit(
            f"{name}: the token stream ({tokens.size} tokens, largest id "
            f"{int(tokens.max())}) does not fit a step of {need} tokens "
            f"over a vocabulary of {cfg.vocab_size}")
    data = {"dataset": "token_stream", "source": source,
            "num_tokens": int(tokens.size),
            "tokens_per_step": need - 1}
    opt, state = model.init_state(cfg, seed=run.seed)
    stream = _placed(tokens, jnp.int32)
    if run.scan_chunk > 1:
        run = _chunk_run(run)
    stepper, spc = _chunked(
        run, lambda st: model.train_step(cfg, opt, st, stream))
    stepper = add_gauges(cfg, stepper, spc, run.eval_every or 50)
    state, loss = _train_loop(run, state, stepper, steps_per_call=spc,
                              data=data)
    return cfg, state, loss, data


def run_looplm(run: RunConfig, overrides: dict):
    from hyperspace_tpu.models import looplm

    cfg, state, loss, data = _run_token_lm(
        run, overrides, "looplm", looplm, _looplm_config, _looplm_gauges)
    stats = looplm.read_stats(cfg, state.stats)
    return {"workload": "looplm", **data, "steps": int(state.step),
            "loss": float(loss), "ce": stats["ce"],
            "exit_prob": stats["exit_prob"],
            "grad_norm": stats["grad_norm"]}


# keys of a published Laguna ``config.json`` that this trainer holds fixed
# (as the CLI's yaml loader prints them), and keys a training step does
# not act on: the model-wide head count (``num_attention_heads_per_layer``
# gives each layer's), and the sparse step and dense layers, which
# ``mlp_layer_types`` states layer by layer (checked against it below)
_MOE_LM_FIXED = {"attention_bias": "False",
                 "tie_word_embeddings": "False", "gating": "per-head",
                 "moe_apply_router_weight_on_input": "False",
                 "moe_router_logit_softcapping": "0", "hidden_act": "silu",
                 "decoder_sparse_step": "1"}
# the same of a published GLM-4.7-Flash (``glm4_moe_lite``) config.json:
# one routing group (n_group > 1 would choose groups first), sigmoid
# scores with a selection bias (noaux_tc), rotary over the whole rope
# slice with no scaling
_GLM_FIXED = {"attention_bias": "False", "tie_word_embeddings": "False",
              "hidden_act": "silu", "topk_method": "noaux_tc",
              "n_group": "1", "topk_group": "1",
              "partial_rotary_factor": "1", "rope_scaling": "None"}
# glm4_moe_lite's keys under MoELMConfig's names
_GLM_NAMES = {"n_routed_experts": "num_experts",
              "routed_scaling_factor": "moe_routed_scaling_factor",
              "rope_theta": "rope_theta_latent"}


def _glm_keys(overrides: dict) -> dict:
    """A published ``glm4_moe_lite`` config's keys as MoELMConfig's:
    every layer latent, the first ``first_k_dense_replace`` dense, one
    shared expert of ``n_shared_experts`` x ``moe_intermediate_size``
    (a sum of SwiGLUs is one SwiGLU over their joined width), sigmoid
    scoring.  A key this trainer would not act on as published is
    refused."""
    from hyperspace_tpu.models import moe_lm

    for key, want in _GLM_FIXED.items():
        got = str(overrides.pop(key, want))
        if got != want:
            raise SystemExit(f"moe_lm: glm4_moe_lite {key}={got!r} is not "
                             f"supported (this trainer runs {key}={want})")
    heads = int(overrides.pop("num_attention_heads", 1))
    kv = int(overrides.pop("num_key_value_heads", heads))
    if kv != heads:
        raise SystemExit(f"moe_lm: glm4_moe_lite num_key_value_heads={kv}: "
                         "latent attention decompresses K and V for every "
                         f"one of the {heads} heads")
    shared = int(overrides.pop("n_shared_experts", 1))
    if shared < 1:
        raise SystemExit("moe_lm: glm4_moe_lite n_shared_experts=0: every "
                         "sparse layer here has a shared expert")
    if "moe_intermediate_size" in overrides:
        overrides["shared_expert_intermediate_size"] = str(
            shared * int(overrides["moe_intermediate_size"]))
    n = int(overrides.get("num_hidden_layers",
                          moe_lm.MoELMConfig.num_hidden_layers))
    dense = int(overrides.pop("first_k_dense_replace", 0))
    out = {_GLM_NAMES.get(k, k): v for k, v in overrides.items()}
    out.update(
        layer_types=json.dumps(["latent_attention"] * n),
        mlp_layer_types=json.dumps(["dense"] * min(dense, n)
                                   + ["sparse"] * max(n - dense, 0)),
        num_attention_heads_per_layer=json.dumps([heads] * n),
        scoring_func="sigmoid")
    return out


def _moe_lm_rope(params: dict) -> dict:
    """The published ``rope_parameters`` as MoELMConfig's fields."""
    full, sliding = params["full_attention"], params["sliding_attention"]
    if full.get("rope_type") not in ("yarn", "default") or sliding.get(
            "rope_type") != "default":
        raise SystemExit(f"moe_lm: rope_parameters {params}: YaRN or "
                         "default rotary on full layers, default on "
                         "sliding layers")
    yarn = ((float(full["factor"]), int(full["original_max_position_"
                                             "embeddings"]),
             float(full["beta_fast"]), float(full["beta_slow"]),
             float(full["attention_factor"]))
            if full["rope_type"] == "yarn" else ())
    return {"rope_theta_full": str(full["rope_theta"]),
            "partial_rotary_full": str(full.get("partial_rotary_factor", 1)),
            "rope_yarn_full": json.dumps(yarn),
            "rope_theta_sliding": str(sliding["rope_theta"]),
            "partial_rotary_sliding": str(
                sliding.get("partial_rotary_factor", 1))}


def _literal(text: str):
    """A value the yaml loader wrote back as text: JSON, or a Python
    literal (a mapping prints as one)."""
    import ast

    try:
        return json.loads(text)
    except ValueError:
        return ast.literal_eval(text)


def _moe_lm_config(run: RunConfig, overrides: dict):
    """(MoELMConfig, stream arguments) from a published config's keys plus
    the job's."""
    from hyperspace_tpu.models import moe_lm

    overrides = dict(overrides)
    model_type = str(overrides.pop("model_type", "laguna"))
    if model_type == "glm4_moe_lite":
        overrides = _glm_keys(overrides)
    elif model_type != "laguna":
        raise SystemExit(f"moe_lm: model_type={model_type!r} is not "
                         "supported (this trainer runs laguna and "
                         "glm4_moe_lite)")
    for key, want in _MOE_LM_FIXED.items():
        got = str(overrides.pop(key, want))
        if got != want:
            raise SystemExit(f"moe_lm: {key}={got!r} is not supported "
                             f"(this trainer runs {key}={want})")
    overrides.pop("num_attention_heads", None)
    dense_at = overrides.pop("mlp_only_layers", None)
    gating = _literal(overrides.pop("gating_types", "[]"))
    if "rope_parameters" in overrides:
        overrides.update(_moe_lm_rope(_literal(
            overrides.pop("rope_parameters"))))
    max_pos = int(overrides.pop("max_position_embeddings", 0))
    stream_kw = {"num_tokens": int(overrides.pop("stream_tokens", 1 << 16))}
    try:
        cfg = apply_overrides(moe_lm.MoELMConfig(),
                              _precision_default(run, overrides))
    except ValueError as e:  # MoELMConfig's own checks
        raise SystemExit(f"moe_lm: {e}") from None
    n = cfg.num_hidden_layers
    if set(gating[:n]) - {"per_head"}:
        raise SystemExit(f"moe_lm: gating_types {sorted(set(gating))}: "
                         "only per_head gates are supported")
    dense = [i for i in range(n) if cfg.mlp_layer_types[i] == "dense"]
    if dense_at is not None and [
            i for i in _literal(dense_at) if i < n] != dense:
        raise SystemExit(f"moe_lm: mlp_only_layers {dense_at} disagrees "
                         f"with mlp_layer_types {cfg.mlp_layer_types[:n]}")
    if max_pos and cfg.sequence_length > max_pos:
        raise SystemExit(f"moe_lm: sequence_length={cfg.sequence_length} "
                         f"exceeds max_position_embeddings={max_pos}")
    return cfg, stream_kw


def _moe_lm_gauges(cfg, stepper, spc: int, every: int):
    """``stepper`` with the registry's moe_lm/* gauges set from the
    state's stats vector at each log boundary."""
    from hyperspace_tpu import precision as precision_mod
    from hyperspace_tpu.models import moe_lm
    from hyperspace_tpu.telemetry import registry as telem

    tokens = cfg.sequence_length * cfg.sequences_per_step
    telem.set_gauge("moe_lm/tokens_per_step", tokens)
    if cfg.mtp_kind:
        telem.set_gauge("moe_lm/mtp_positions_per_step",
                        (cfg.sequence_length - 1) * cfg.sequences_per_step)
    kinds = [cfg.kind_of(i) for i in range(cfg.num_hidden_layers)]
    latent = [k for k in kinds + [cfg.mtp_kind]
              if k and k.startswith("latent")]
    if latent:
        # the per-head K and V one forward pass decompresses for the
        # flash calls, on the compute lane
        telem.set_gauge("moe_lm/mla_kv_decompressed_bytes", (
            len(latent) * tokens * cfg.heads_of(latent[0]) * 2
            * cfg.v_head_dim * jnp.dtype(precision_mod.get_policy(
                cfg.precision).compute).itemsize))

    def gauges(state):
        if not cfg.sparse_layers:
            return
        rows = moe_lm.read_stats(cfg, state.stats)["held_rows"]  # hyperlint: disable=host-sync-in-hot-path — once a log boundary, beside the loop's own loss fetch
        telem.set_gauge("moe_lm/max_held_rows", max(rows))
        telem.set_gauge("moe_lm/mean_held_rows", sum(rows) / len(rows))
        telem.set_gauge("moe_lm/held_share", sum(rows) / (
            len(rows) * tokens * cfg.num_experts_per_tok))

    return _at_log_boundaries(stepper, spc, every, gauges)


def run_moe_lm(run: RunConfig, overrides: dict):
    from hyperspace_tpu.models import moe_lm

    cfg, state, loss, data = _run_token_lm(
        run, overrides, "moe_lm", moe_lm, _moe_lm_config, _moe_lm_gauges)
    stats = moe_lm.read_stats(cfg, state.stats)
    return {"workload": "moe_lm", **data, "steps": int(state.step),
            "loss": float(loss), "grad_norm": stats["grad_norm"],
            "held_rows": stats["held_rows"],
            **{k: stats[k] for k in ("main_loss", "mtp_loss") if k in stats}}


WORKLOADS = {
    "looplm": run_looplm,
    "moe_lm": run_moe_lm,
    "poincare": run_poincare,
    "hgcn": run_hgcn,
    "hybonet": run_hybonet,
    "hvae": run_hvae,
    "product": run_product,
}


# --- helpers ------------------------------------------------------------------


def _closure_dataset(run: RunConfig, branching: int):
    """(dataset, data record) for the closure-embedding workloads: the
    ``data_root`` TSV, else a small synthetic tree that stands in for
    tests — the record says which one ran and at what size (manifest
    and result)."""
    from hyperspace_tpu.data import wordnet

    if run.data_root:
        ds, source = wordnet.load_closure_tsv(run.data_root), "disk"
    else:
        ds, source = wordnet.synthetic_tree(depth=5,
                                            branching=branching), "synthetic"
    return ds, {"source": source, "num_nodes": int(ds.num_nodes),
                "num_pairs": int(ds.num_pairs)}


def _train_loop(run: RunConfig, state, stepper, project=None,
                steps_per_call=1, health_fn=None, data=None):
    """The ONE step loop every workload runner goes through — moved to
    :func:`hyperspace_tpu.train.loop.run_loop` (checkpoint/resume, JSONL
    logging with boundary-crossing cadence, per-chunk loss accumulation,
    telemetry spine); this thin wrapper keeps the import lazy so
    ``--help`` never pays for orbax.  A stepper carrying an
    ``on_rollback`` hook (the stream steppers do) hands it to the
    divergence guard — docs/resilience.md."""
    from hyperspace_tpu.train.loop import run_loop

    return run_loop(run, state, stepper, project=project,
                    steps_per_call=steps_per_call, health_fn=health_fn,
                    on_rollback=getattr(stepper, "on_rollback", None),
                    data=data)


def _placed(a: np.ndarray, dtype=None):
    """``jnp.asarray`` under a ``place`` span: a runner's own copy of
    its supervision data (pairs, the token stream) to the device."""
    from hyperspace_tpu.telemetry.trace import span

    info = {}
    with span("place", info):
        out = jnp.asarray(a, dtype)
        info["bytes"] = int(out.nbytes)
    return out


def _maybe_health(run: RunConfig, build):
    """``build() -> jitted health fn`` only when sampling is on — the
    health program never compiles for runs that will not use it."""
    return build() if run.health_every > 0 else None


def _make_health(tags=None, params_of=None):
    from hyperspace_tpu.telemetry.health import make_health_fn

    return make_health_fn(tags, params_of=params_of)


def _eval_span():
    """Trace span around a runner's final evaluation (host timeline
    completeness: eval time is part of the run artifact)."""
    from hyperspace_tpu.telemetry.trace import span

    return span("eval")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="hyperspace_tpu.cli.train",
        description="Train a hyperspace-tpu workload.")
    ap.add_argument("workload", choices=sorted(WORKLOADS))
    ap.add_argument("overrides", nargs="*",
                    help="key=value overrides (run- or workload-config)")
    ap.add_argument("--yaml", default=None,
                    help="YAML file of overrides (CLI wins on conflict)")
    args = ap.parse_args(argv)

    pairs = []
    if args.yaml:
        import yaml

        with open(args.yaml) as f:
            doc = yaml.safe_load(f) or {}
        pairs += [f"{k}={json.dumps(v) if isinstance(v, list) else v}"
                  for k, v in doc.items()]
    pairs += args.overrides

    run, wl_overrides = split_overrides(pairs, RunConfig())
    from hyperspace_tpu import compile_cache, precision as precision_mod

    try:
        precision_mod.get_policy(run.precision)
    except ValueError as e:  # a typo'd preset is a usage error
        raise SystemExit(str(e)) from None
    if run.metrics_out and run.metrics_every <= 0:
        raise SystemExit(
            f"metrics_every={run.metrics_every}: want a positive "
            "snapshot cadence in seconds")
    try:
        # BEFORE any workload compile: every executable this run builds
        # should land in (or come from) the persistent cache
        compile_cache.activate(run.compile_cache_dir)
    except ValueError as e:  # unusable cache dir is a usage error
        raise SystemExit(str(e)) from None
    if run.rollback > 0 and not run.ckpt_dir:
        raise SystemExit(
            "rollback=N needs ckpt_dir= — the divergence guard rewinds "
            "to the last COMMITTED checkpoint (docs/resilience.md)")
    from hyperspace_tpu.resilience import faults as _faults

    try:
        chaos_armed = _faults.install_chaos(run.chaos, run.chaos_seed)
    except ValueError as e:  # malformed chaos= grammar is a usage error
        raise SystemExit(str(e)) from None
    if run.multihost and run.num_processes > 1:
        # the ONE process-group entry point (parallel/multihost.py) —
        # shared with the loopback harness, so CLI pods and the 2-process
        # CPU drills form their groups identically
        from hyperspace_tpu.parallel import multihost as mh

        mh.initialize(run.coordinator, run.num_processes, run.process_id)
    from hyperspace_tpu.telemetry import cli_session

    # where a resumed run starts: with the result's final step count it
    # tells a run that stepped from one with nothing left to do
    start_step = 0
    if run.resume and run.ckpt_dir:
        from hyperspace_tpu.train.checkpoint import peek_latest_step

        start_step = peek_latest_step(run.ckpt_dir)

    # enabled BEFORE the workload runs (not inside run_loop) so host
    # graph prep / cache misses land in the spans and trace too; the
    # trace dumps in cli_session's finally — a crash (incl. health_abort)
    # still produces it, covering everything up to the failure point.
    # Load the JSON at https://ui.perfetto.dev (host-level spans; the
    # XLA-level complement is train/profiling.trace).
    try:
        with cli_session(run.telemetry, run.trace_out):
            result = WORKLOADS[args.workload](run, wl_overrides)
        if chaos_armed:
            result["chaos"] = _faults.stats()
    finally:
        if chaos_armed:
            # the registry is process-global: an in-process caller
            # (tests, benches) must never inherit this run's faults
            _faults.clear()
    loss = result.get("loss")
    diverged = False
    if isinstance(loss, float) and not math.isfinite(loss):
        if result.get("steps", 0) > start_step:
            # the run stepped and ended on a non-finite loss: that is a
            # failed run, and its exit code says so
            diverged = result["diverged"] = True
        else:
            result["no_steps_run"] = True  # resumed past its step budget
    print(json.dumps(_json_safe(result)))
    return 1 if diverged else 0


def _json_safe(x):
    """Non-finite floats → null and numpy scalars → Python, so every
    emitted line is strict JSON (loss is nan when a resumed run had
    nothing left to do — ``no_steps_run``, exit 0 — or when a run
    diverged — ``diverged``, exit 1; a NaN table row reaches the serve
    CLI's response stream the same way — all must print parseably).
    Shared by the train and serve CLIs."""
    if isinstance(x, np.generic):
        x = x.item()
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _json_safe(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_json_safe(v) for v in x]
    return x


if __name__ == "__main__":
    sys.exit(main())
