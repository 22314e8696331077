"""Chunked-dispatch training loop — the production home of the r05 fix.

The r05 roofline study (docs/benchmarks.md, BENCH_r05.json) diagnosed the
small-step workloads (HVAE, product-embed) as pinned at the ~7 ms
per-dispatch latency floor — 10-20x above their HBM-roofline bounds — and
proved the fix (K steps per dispatch under ``lax.scan``) inside
``benchmarks/workloads_bench.py`` only.  This module promotes that bench
trick to a first-class training-loop feature shared by every CLI runner:

- :func:`make_chunked_stepper` compiles K calls of a single-step function
  into ONE XLA program (``lax.scan`` over the step body) with the carried
  train state donated, so a run pays one dispatch per K steps instead of
  one per step.  With the same step body and the same PRNG stream the
  chunked trajectory is bitwise the single-step trajectory (the
  ``train_epoch_scan`` guarantee, now generic).
- :func:`run_loop` is the ONE step loop every workload runner goes
  through (moved here from ``cli/train.py``): checkpoint/resume, JSONL
  logging with boundary-crossing cadence (a chunk that crosses a log or
  save interval fires it), and per-chunk loss accumulation
  (:class:`hyperspace_tpu.optim.metrics.ChunkMetrics` — one host fetch
  per log boundary, never one per step).
- :func:`resume_chunk` derives the batch-stream resume offset (ceil —
  see the function doc; floor would replay already-consumed rows).
- ``run_loop`` is also the telemetry spine (``telemetry=`` on the CLI;
  docs/observability.md): it writes the run manifest as the FIRST JSONL
  record, wraps each dispatch/flush/save in trace spans, snapshots the
  counter registry (``ctr/*``) and span aggregates (``span/*``) into
  every log record, samples the numerical-health monitor every
  ``health_every`` chunks, and closes the stream with one
  ``telemetry_summary`` record.  Disabled (the default) none of that
  runs: the per-dispatch additions are one registry dict-op and a
  no-op span check — no host sync, no extra dispatches (tested).

Chunk size policy: ``K`` trades dispatch amortization against reaction
latency — checkpoints/logs can only land on chunk boundaries, so keep
``K`` ≲ the checkpoint cadence.  K=32 recovers the dispatch floor on the
ms-scale steps (docs/benchmarks.md "chunked dispatch"); K=1 is exactly
the old loop (steppers are called directly, no scan wrapper).
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp


def make_chunked_stepper(step_fn: Callable, chunk_steps: int, policy=None):
    """Compile ``chunk_steps`` calls of ``step_fn`` into one XLA program.

    ``step_fn(state, *args) -> (state, out...)`` must be a traceable
    single-step body (the jitted per-step train functions qualify: jit
    inlines under trace).  Returns ``chunk(state, *args)`` — one jitted
    dispatch running ``chunk_steps`` steps with ``state`` donated —
    whose outputs are the per-step ``out`` values stacked on a leading
    ``[chunk_steps]`` axis (a single extra output comes back as one
    stacked array, several as a tuple of stacked arrays).  ``*args`` are
    scan-invariant (the same batch/graph arrays feed every step in the
    chunk; steps that walk a plan index by ``state.step`` advance
    through it as usual).

    ``policy`` is an optional mixed-precision policy (a
    ``hyperspace_tpu.precision`` Policy or preset name).  With a mixed
    policy the chunk program casts the floating leaves of ``*args`` —
    the batch data every step in the chunk reads — to the policy's
    compute dtype ONCE, outside the scan, so a bf16 run pays one host
    batch downcast per dispatch instead of one per step (integer/bool
    leaves — ids, masks — pass through untouched; the carried ``state``
    is never cast: master params stay in the param dtype).  The per-step
    losses are cast to the accumulation dtype on the way out.  ``None``
    or the f32 preset changes nothing — bit-identical by construction.

    ``chunk_steps <= 1`` returns ``step_fn`` unchanged (the K=1 path is
    the caller's original stepper, bit-identical by construction) except
    under a mixed policy, where a thin wrapper applies the same arg cast
    per call.
    """
    from hyperspace_tpu.precision import get_policy

    pol = get_policy(policy)
    k = int(chunk_steps)
    if k <= 1:
        if not pol.mixed:
            return step_fn

        def one_step(state, *args):
            # same arg-cast AND accum-cast contract as the scanned path,
            # so loss dtype never flips with the scan_chunk setting
            res = step_fn(state, *pol.cast_compute_tree(args))
            return (res[0],) + tuple(pol.cast_accum(o) for o in res[1:])

        return one_step

    def body(state, *args):
        args = pol.cast_compute_tree(args)  # once per chunk, not per step

        def one(st, _):
            res = step_fn(st, *args)
            if len(res) == 2:
                return res[0], pol.cast_accum(res[1])
            return res[0], tuple(pol.cast_accum(o) for o in res[1:])

        return jax.lax.scan(one, state, None, length=k)

    return jax.jit(body, donate_argnums=(0,))


def round_steps_to_chunk(steps: int, chunk_steps: int) -> int:
    """Step budget rounded UP to a chunk multiple: every dispatch runs
    exactly ``chunk_steps`` steps (the scan length is baked into the
    program), so checkpoint/log step numbers always equal the steps
    actually taken — never a clamped lie."""
    k = max(int(chunk_steps), 1)
    return -(-int(steps) // k) * k


def resume_chunk(ckpt_dir: Optional[str], resume: bool,
                 chunk_steps: int) -> int:
    """Starting chunk index for a resuming batch stream (e.g.
    ``hgcn_sampled.SampledBatchStream``): a run resuming from step R has
    consumed batches from chunks 0..ceil(R/cs)-1 (the last possibly
    partially), so the stream skips to the NEXT chunk boundary —
    restarting at 0 would replay the consumed chunks, and floor division
    would re-serve the already-started boundary chunk's first R%cs rows
    (ADVICE r04).  The skipped tail rows of a partial boundary chunk are
    iid draws that simply never get used; no batch is ever repeated."""
    if not (ckpt_dir and resume):
        return 0
    from hyperspace_tpu.train.checkpoint import peek_latest_step

    cs = max(int(chunk_steps), 1)
    return -(-peek_latest_step(ckpt_dir) // cs)


def _logger(run):
    from hyperspace_tpu.train.logging import MetricsLogger

    if jax.process_index() != 0:
        # multi-process runs: every process computes IDENTICAL losses
        # (DP steps end in an all-reduce), so N processes writing the
        # same JSONL/TB path would race each other for no information —
        # the run log is a process-0 artifact (docs/multihost.md)
        return MetricsLogger(None, stdout=False, tensorboard_dir=None)
    return MetricsLogger(run.log, stdout=False,
                         tensorboard_dir=run.tensorboard_dir)


def run_manifest(run, data: Optional[dict] = None) -> dict:
    """The run-identity record logged FIRST in every telemetry-enabled
    JSONL (the acceptance anchor for "which run produced this file"):
    full run config, device/backend identity, the kernel implementation
    that identity resolved to (``kernels._support.mode()`` — a run whose
    kernels ran as their XLA twins says so here), process topology, the
    package version, and — from the runners — ``data``: which dataset
    ran (``source``: disk or a synthetic stand-in) and at what size."""
    import dataclasses

    import jax

    import hyperspace_tpu
    from hyperspace_tpu.kernels import _support

    try:
        config = dataclasses.asdict(run)
    except TypeError:  # duck-typed run object (tests)
        config = {k: v for k, v in vars(run).items()
                  if not k.startswith("_")}
    dev = jax.devices()[0]
    return {
        "config": config,
        "backend": jax.default_backend(),
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "kernel_mode": _support.mode(),
        "data": data,
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "version": hyperspace_tpu.__version__,
    }


def _telemetry_setup(run):
    """(tracer, registry, freshly_enabled) per the run's flags — all
    None/disabled by default.  Duck-typed getattr so non-CLI callers
    (tests, benches) opt in by simply having the attributes.
    ``freshly_enabled`` marks that THIS call turned the process-global
    tracer on (library use; the CLI enables it earlier, in ``main``, so
    host prep spans record too) — the loop then turns it back off on
    exit instead of leaking span recording into later runs."""
    telemetry_on = bool(getattr(run, "telemetry", False))
    trace_out = getattr(run, "trace_out", None)
    tracer = reg = None
    fresh = False
    if telemetry_on or trace_out:
        from hyperspace_tpu.telemetry import registry, trace

        fresh = not trace.default_tracer().enabled
        tracer = trace.enable(keep_events=bool(trace_out))
        if fresh:
            # library use: the tracer was off, so anything it holds is a
            # PRIOR run's aggregates/events — this run starts clean
            tracer.reset()
        reg = registry.default_registry() if telemetry_on else None
    return tracer, reg, fresh


@contextlib.contextmanager
def _tracer_guard(tracer, fresh, trace_out=None):
    """Return the process-global tracer to its pre-run state when this
    run_loop enabled it: dump the requested trace file (the CLI flow
    dumps later, in ``main``, so the eval span makes the timeline — a
    library caller's only dump point is here), drop unflushed boundary
    aggregates (they would bleed into a later run's first record), and
    disable recording."""
    try:
        yield
    finally:
        if tracer is not None and fresh:
            if trace_out:
                try:
                    tracer.dump_chrome_trace(trace_out)
                except OSError:
                    pass  # diagnostics never sink (or mask) the run
            tracer.flush_fields()
            tracer.enabled = False


def _health_monitor(run, health_fn):
    if health_fn is None or int(getattr(run, "health_every", 0) or 0) <= 0:
        return None, 0
    from hyperspace_tpu.telemetry.health import (
        DEFAULT_BOUNDARY_EPS, DEFAULT_VIOLATION_TOL, HealthMonitor)

    hm = HealthMonitor(
        health_fn,
        boundary_eps=float(getattr(run, "health_eps",
                                   DEFAULT_BOUNDARY_EPS)),
        violation_tol=float(getattr(run, "health_tol",
                                    DEFAULT_VIOLATION_TOL)),
        abort=bool(getattr(run, "health_abort", False)))
    return hm, int(run.health_every)


def _poison(state, loss):
    """Apply the ``train.step_nan`` fault: NaN every inexact leaf of
    the state and the loss — the device-side shape a poisoned batch
    leaves behind after one step has propagated it."""
    def p(a):
        a = jnp.asarray(a)
        if jnp.issubdtype(a.dtype, jnp.inexact):
            return a * jnp.asarray(jnp.nan, a.dtype)
        return a

    return jax.tree_util.tree_map(p, state), p(loss)


def _rollback_ctrl(run, ck, project, on_rollback):
    """RollbackController per the run's ``rollback``/``rollback_lr_backoff``
    flags (None when off).  ``rollback=N`` needs a checkpoint dir — the
    rollback target IS the last committed checkpoint."""
    max_rb = int(getattr(run, "rollback", 0) or 0)
    if max_rb <= 0:
        return None
    if ck is None:
        raise ValueError(
            "rollback=N needs ckpt_dir= — the divergence guard rewinds "
            "to the last COMMITTED checkpoint (docs/resilience.md)")
    from hyperspace_tpu.resilience.guard import RollbackController

    return RollbackController(
        ck, max_rollbacks=max_rb,
        lr_backoff=float(getattr(run, "rollback_lr_backoff", 0.5) or 0.5),
        project=project, on_rollback=on_rollback)


def run_loop(run, state, stepper, project=None, steps_per_call=1,
             health_fn=None, on_rollback=None, data=None):
    """Shared step loop: optional checkpoint/resume + JSONL logging.

    ``run`` is duck-typed (``cli.train.RunConfig`` shape): ``steps``,
    ``eval_every``, ``log``, ``tensorboard_dir``, ``ckpt_dir``,
    ``ckpt_every``, ``resume``; plus the optional telemetry knobs
    ``telemetry``, ``trace_out``, ``health_every``/``health_eps``/
    ``health_abort`` (absent = off) and the divergence-guard knobs
    ``rollback`` (max rollbacks; 0 = off) / ``rollback_lr_backoff``
    (docs/resilience.md).  With the guard on, a non-finite loss at a
    metrics/save boundary — or a health-threshold violation at the
    health cadence — rewinds to the last committed checkpoint instead
    of poisoning the rest of the run; ``on_rollback(restored_step,
    attempt, lr_scale)`` lets stream-fed callers re-seed past the
    poisoned chunk and apply the LR backoff.  Every workload runner goes through
    here, so --ckpt-dir / resume work uniformly.  The checkpoint manager
    is context-managed (its __exit__ waits for in-flight async saves and
    closes background threads, also on the exception path).  Orbax async
    saves copy device→host synchronously before returning, so saving a
    state whose buffers the next step's donation invalidates is safe.
    ``project`` re-projects restored states onto their manifolds
    (train/checkpoint.py's restore contract — guards dtype/float drift
    off the constraint surface).  ``steps_per_call`` is the chunk size:
    the stepper always executes exactly that many steps per call (see
    :func:`make_chunked_stepper`); chunked steppers return the stacked
    ``[steps_per_call]`` per-step losses, of which the LAST is the
    logged/returned loss and the chunk mean/last/min/max ride along as
    ``loss_*`` fields.  ``health_fn`` is a jitted ``state -> {name:
    device scalar}`` (``telemetry.health.make_health_fn``), sampled
    every ``run.health_every`` chunks — reading the state between
    dispatches is safe w.r.t. donation (the read is enqueued before the
    next dispatch consumes the buffers).  ``data`` (which dataset ran,
    at what size) rides into the run manifest.  Returns ``(final_state,
    final_loss)``; loss is nan when no step ran.
    """
    from hyperspace_tpu.resilience import faults
    from hyperspace_tpu.telemetry import registry as telem
    from hyperspace_tpu.telemetry import trace
    from hyperspace_tpu.telemetry.trace import span, tracing

    tracer, reg, fresh_tracer = _telemetry_setup(run)
    # the process's start-up timeline ends where its first loop's first
    # dispatch returns (telemetry/trace.py): read once a call; a step
    # checks the local `startup_open`, which that dispatch clears
    ends_startup = startup_open = trace.startup_open()
    # profile_steps=N: for the first N steps, block on each chunk's
    # result inside the dispatch window (the phase reads execution, not
    # async enqueue) and observe it as the device_step phase — the
    # train-plane mirror of the serve stage histograms (compile events
    # are counted from the package's import on, so the profiled window
    # attributes compile time).  N steps only: a permanent block would
    # re-serialize host and device, the exact overlap the chunked loop
    # exists to buy.
    profile_steps = int(getattr(run, "profile_steps", 0) or 0)
    monitor, health_every = _health_monitor(run, health_fn)
    mwriter = None
    metrics_out = (getattr(run, "metrics_out", None)
                   if jax.process_index() == 0 else None)
    if metrics_out:
        # Prometheus-text file snapshotter (telemetry/exposition.py):
        # a training job becomes scrapeable-by-file; checked at chunk
        # boundaries (one clock read each), final write on exit
        from hyperspace_tpu.telemetry.exposition import MetricsFileWriter

        mwriter = MetricsFileWriter(
            metrics_out, float(getattr(run, "metrics_every", 30.0)))
    ck = None
    start = 0
    loss = jnp.nan
    if run.ckpt_dir:
        from hyperspace_tpu.train.checkpoint import CheckpointManager

        ck = CheckpointManager(run.ckpt_dir,
                               save_interval_steps=run.ckpt_every)
    ctrl = _rollback_ctrl(run, ck, project, on_rollback)
    acc = None
    if steps_per_call > 1:
        from hyperspace_tpu.optim.metrics import ChunkMetrics

        acc = ChunkMetrics()

    # per-run counter baseline, mirroring the tracer's fresh/guard
    # semantics: when THIS run_loop freshly enabled telemetry (library
    # use — several runs share the process-cumulative registry), report
    # counters as deltas from loop entry so run 2 never claims run 1's
    # dispatches.  In the CLI flow telemetry comes up in main() before
    # graph prep, so no baseline is taken and pre-loop prep/prefetch
    # counts rightly belong to this run's records.
    counter_base = (reg.mark()
                    if (reg is not None and fresh_tracer) else None)

    def do_rollback(st, dn, log, reason):
        """The ONE rollback sequence every trigger funnels through:
        discard the poisoned interval's chunk-metric accumulation, then
        rewind — callers rebind (state, done), set loss = nan and
        continue."""
        if acc is not None:
            acc.flush()  # poisoned interval: discard
        return ctrl.rollback(st, dn, log, reason=reason)

    def record_fields():
        """Telemetry fields for one JSONL record: span aggregates since
        the last record + a consistent counter/gauge snapshot."""
        if reg is None:
            return {}
        out = tracer.flush_fields() if tracer is not None else {}
        out.update(reg.snapshot("ctr/", baseline=counter_base))
        return out

    # restore inside the with-block: a corrupt checkpoint raising in
    # restore() still closes the manager's async machinery on the way out
    # (tracer guard FIRST so it unwinds last, after the logger closed)
    with _tracer_guard(tracer, fresh_tracer,
                       getattr(run, "trace_out", None)), \
            (ck if ck is not None else contextlib.nullcontext()), \
            _logger(run) as log:
        if reg is not None:
            log.event("run_manifest", **run_manifest(run, data))
        if (ck is not None and run.resume
                and ck.latest_committed_step() is not None):
            state, start = ck.restore(state, project=project)
            # re-materialize the restored pytree before stepping: the
            # first dispatch DONATES these buffers, and donating arrays
            # that came out of orbax's restore machinery (rather than out
            # of a jitted program) has been observed to corrupt resumed
            # trajectories under a persistent compilation cache; one
            # device-side copy per resume buys unconditionally safe
            # donation
            state = jax.tree_util.tree_map(
                lambda a: jnp.asarray(a).copy(), state)
        if ctrl is not None and ck.latest_committed_step() is None:
            # the guard needs a rollback target from chunk one: without
            # a committed checkpoint the first divergence would be fatal
            ck.save(start, state, force=True)
        last_saved = None
        every = run.eval_every or 50
        done = start
        chunk_i = 0
        prof_until = start + profile_steps
        while True:
            while done < run.steps:
                # span args: step-at-dispatch + chunk size, so a slow
                # span in the Perfetto timeline is attributable to its
                # position (built only while tracing — the disabled hot
                # path stays allocation-free)
                args = ({"step": done, "chunk": steps_per_call}
                        if tracing() else None)
                prof = profile_steps > 0 and done < prof_until
                # one iteration = one step marker in a device profile: its
                # self time (no child span covers it) is the loop's own host
                # work — back-pressure waits sit inside `dispatch`
                with span("train_step", args=args, step_num=done):
                    t_disp = time.perf_counter()
                    with span("dispatch", args=args):
                        state, loss = stepper(state)
                        if prof:
                            # profiled window: the dispatch time must read
                            # execution, not enqueue (block_until_ready is
                            # not a host fetch — no value crosses to the host)
                            jax.block_until_ready(loss)
                    disp_ms = (time.perf_counter() - t_disp) * 1e3
                    if startup_open:
                        trace.close_startup()
                        startup_open = False
                    telem.observe("train/dispatch_ms", disp_ms)
                    if prof:
                        telem.observe("train/phase/device_step_ms", disp_ms)
                    telem.inc("train/dispatches")
                    if mwriter is not None:
                        try:
                            mwriter.maybe_write()
                        except OSError:
                            pass  # scrape-file loss never sinks the run
                    if faults.active() and faults.poison("train.step_nan"):
                        # chaos: the device-side shape one poisoned batch
                        # leaves after its step (docs/resilience.md)
                        state, loss = _poison(state, loss)
                    chunk_i += 1
                    if acc is not None:
                        acc.add(loss)
                    if jnp.ndim(loss):  # scanned chunk: [spc] losses
                        loss = loss[-1]
                    # the stepper always executes exactly steps_per_call
                    # steps (the scan length is baked into the program), so
                    # the recorded step count is the TRUE count — never
                    # clamped
                    prev, done = done, done + steps_per_call
                    # boundary-crossing gates: with chunked stepping, `done`
                    # only takes chunk multiples, so exact-equality cadence
                    # would degrade to lcm(chunk, interval); fire whenever
                    # the chunk crossed an interval boundary (identical to
                    # the old `done % every == 0` when steps_per_call == 1)
                    if (done // every) > (prev // every):
                        # the float(loss) fetch is the interval's real
                        # block-until-device-done (dispatch is async
                        # enqueue), so it must sit INSIDE the span or the
                        # wait would show up nowhere in the span breakdown
                        t_flush = time.perf_counter()
                        with span("metrics_flush"):
                            kw = {"loss": float(loss)}  # hyperlint: disable=host-sync-in-hot-path — the documented per-boundary fetch
                            if acc is not None:
                                stats = acc.flush()
                                if stats is not None:
                                    kw.update(stats)
                        telem.observe("train/metrics_flush_ms",
                                      (time.perf_counter() - t_flush) * 1e3)
                        if ctrl is not None and ctrl.divergent(kw["loss"]):
                            # the poisoned interval's record is the incident
                            # event, not a loss row
                            state, done = do_rollback(
                                state, done, log,
                                f"non-finite loss at step {done}")
                            loss = jnp.nan
                            continue
                        log.log(done, **kw, **record_fields())
                    # health sampling rides the chunk cadence, not the log
                    # one: a diverging run should flag BEFORE the next log
                    # boundary
                    if monitor is not None and chunk_i % health_every == 0:
                        if ctrl is None:
                            monitor.check(state, done, log)
                        else:
                            # guard mode: a threshold violation (or the
                            # monitor's own abort) is a rollback trigger,
                            # not a warning/abort — until the budget runs out
                            try:
                                bad = monitor.problems(
                                    monitor.check(state, done, log))
                            except FloatingPointError as e:
                                bad = [str(e)]
                            if bad:
                                state, done = do_rollback(
                                    state, done, log,
                                    "health: " + "; ".join(bad))
                                loss = jnp.nan
                                continue
                    # ckpt_every <= 0 = final save only (mirrors
                    # eval_every's "0 = eval only at the end"; orbax's
                    # interval gate divides by the interval, so it never
                    # sees a 0)
                    if ck is not None and run.ckpt_every > 0:
                        iv = run.ckpt_every
                        crossed = (done // iv) > (prev // iv)
                        if ctrl is not None and crossed:
                            # guard-only fetch: a poisoned state must never
                            # be saved — it would become the rollback target
                            lv = float(loss)  # hyperlint: disable=host-sync-in-hot-path — guard mode only, once per save boundary
                            if ctrl.divergent(lv):
                                state, done = do_rollback(
                                    state, done, log,
                                    f"non-finite loss at save boundary, "
                                    f"step {done}")
                                loss = jnp.nan
                                continue
                        if ck.save(done, state,
                                   force=crossed and steps_per_call > 1):
                            last_saved = done
            # end-of-run divergence check: a chunk past the last crossed
            # boundary can still be poisoned — never close (or final-
            # save) a diverged run while the guard has budget left
            if ctrl is not None and done > start:
                lv = float(loss)
                if ctrl.divergent(lv):
                    state, done = do_rollback(
                        state, done, log,
                        f"non-finite loss at run end, step {done}")
                    loss = jnp.nan
                    continue
            break
        if acc is not None and done > start:
            # chunks past the last crossed log boundary would otherwise
            # vanish: close the run with a final record so every step's
            # loss lands in some interval's loss_mean
            t_flush = time.perf_counter()
            with span("metrics_flush"):
                stats = acc.flush()
                final_loss = float(loss)  # hyperlint: disable=host-sync-in-hot-path — the run-closing boundary fetch
            telem.observe("train/metrics_flush_ms",
                          (time.perf_counter() - t_flush) * 1e3)
            if stats is not None:
                log.log(done, loss=final_loss, **stats, **record_fields())
        if ck is not None and start < run.steps and last_saved != done:
            # the final state must land even when it misses the save
            # cadence — otherwise resume silently replays a partial chunk
            ck.save(done, state, force=True)
        if ends_startup:
            trace.close_startup()  # a run that dispatched nothing
        if reg is not None:
            if ck is not None:
                ck.wait()  # async saves landed → ckpt/bytes gauge is real
            summary = reg.snapshot("ctr/", baseline=counter_base)
            if tracer is not None:
                summary.update(tracer.total_fields())
            if ends_startup:
                # process start to this loop's first dispatch, by span
                startup = trace.startup_fields()
                log.event("startup", **startup)
                summary.update(startup)
            # each local device's allocator reading while the training
            # state and its data are still alive (live arrays; None
            # where the backend keeps no statistics, as the CPU's) —
            # on a mesh it shows whether the data was divided
            summary["device_memory"] = [
                {"id": d.id, "bytes_in_use": st.get("bytes_in_use"),
                 "peak_bytes_in_use": st.get("peak_bytes_in_use")}
                for d, st in ((d, d.memory_stats() or {})
                              for d in jax.local_devices())]
            if jax.process_count() > 1:
                # fleet view (docs/observability.md "Multihost metric
                # aggregation", exercised by real training since this
                # loop went multi-process): every process contributes
                # its raw export over ONE allgather; counters sum,
                # gauges max — logged process-0-side as fleet/* fields
                from hyperspace_tpu.parallel.multihost import (
                    gather_metric_exports)
                from hyperspace_tpu.telemetry.aggregate import merge_exports

                fc, fg, _ = merge_exports(gather_metric_exports(reg))
                summary["fleet_processes"] = jax.process_count()
                summary.update({f"fleet/{k}": v for k, v in fc.items()})
                summary.update({f"fleet/{k}": v for k, v in fg.items()})
            log.event("telemetry_summary", steps=int(done), **summary)
        if mwriter is not None:
            try:
                # the run's final counters must land whatever the
                # cadence — the last scrape a collector sees is the
                # run's closing state
                mwriter.write()
            except OSError:
                pass
    return state, loss
