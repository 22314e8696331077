"""Benchmark entry point — the LAST stdout line is a compact JSON headline.

Output contract (VERDICT r4 missing #1): the driver records only the final
~2000 characters of stdout, so the FINAL line is a compact self-sufficient
headline record (``compact_headline``, hard-capped at ``COMPACT_LIMIT``
chars) and the full ever-growing detail record precedes it (and is written
to ``bench_full.json``).  ``tests/test_bench_cli.py`` asserts the tail
contract so it cannot regress.

Metrics tracked (BASELINE.json "metric"): HGCN samples/sec/chip on
ogbn-arxiv-scale graphs, and Poincaré-embedding epoch time; serving
throughput (``serve_qps`` — queries/s through the batcher + engine) rides
in detail under ``--metric auto`` and is selectable as the headline with
``--metric serve``.  The primary reported metric is selected by
``--metric`` (default: the first available in priority order
hgcn > poincare).  ``vs_baseline`` is null because BASELINE.json
``published`` is empty — no reference number exists in this environment
(SURVEY.md §6).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import signal
import sys
import threading
import time

# wall-clock budget (seconds) for the WHOLE bench run, env-tunable via
# BENCH_BUDGET_S / --budget-s.  BENCH_r05.json was rc=124 with
# ``parsed: null`` — the driver's hard timeout killed the process before
# any JSON landed, losing the whole round's reading; on that round's
# experimental backend even the watchdog timer was starved (native code
# holding the GIL).  Three defenses, layered: (1) the default budget
# sits WELL under the 870 s driver timeout so a slow backend still has
# ~2x headroom, (2) every leg — including the headline benchmark — runs
# under a SIGALRM deadline derived from the remaining budget (a signal
# interrupts Python-level work a threading.Timer can't reach), and
# (3) the last-resort watchdog thread emits whatever completed and
# exits 0 instead of dying unparsed.
DEFAULT_BUDGET_S = 420.0


class _LegTimeout(BaseException):
    """Raised by the SIGALRM deadline inside an over-budget leg.

    Derives from ``BaseException`` (like ``KeyboardInterrupt``) on
    purpose: the benched code is full of defensive ``except Exception``
    blocks (diagnostics, cache fallbacks), and the one-shot alarm firing
    inside one of those must not be swallowed there — the leg would run
    unbounded with the alarm already spent, recreating the BENCH_r05
    overrun this deadline exists to close."""


@contextlib.contextmanager
def _deadline(seconds: float):
    """Hard per-leg deadline: raise :class:`_LegTimeout` in the main
    thread after ``seconds`` via SIGALRM — unlike the watchdog's timer
    thread this interrupts pure-Python overruns (sleeps, slow host prep,
    long sampling loops) at the deadline, not at the next thread switch.
    No-op off the main thread or where SIGALRM does not exist."""
    if (not hasattr(signal, "SIGALRM")
            or threading.current_thread() is not threading.main_thread()):
        yield
        return

    def _raise(signum, frame):
        raise _LegTimeout(f"leg deadline after {seconds:.1f}s")

    old = signal.signal(signal.SIGALRM, _raise)
    signal.setitimer(signal.ITIMER_REAL, max(seconds, 0.001))
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


class _BudgetGuard:
    """Deadline bookkeeping + the emit-once watchdog."""

    def __init__(self, seconds: float):
        self.budget_s = float(seconds)
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._claimed = False
        self._timer = None

    def elapsed(self) -> float:
        return time.perf_counter() - self._t0

    def remaining(self) -> float:
        return self.budget_s - self.elapsed()

    def claim_emit(self) -> bool:
        """True exactly once — whoever wins prints the artifact."""
        with self._lock:
            if self._claimed:
                return False
            self._claimed = True
            return True

    def arm(self, holder: dict, _exit=os._exit):
        """Watchdog: at the deadline, emit the best record available —
        the in-progress result (legs completed so far) or a bare
        budget_exhausted record — and exit 0.  The main path disarms it
        after its own emit, so the timer only ever fires on a run that
        would otherwise die to the driver's hard timeout with nothing
        parseable on stdout.  (``_exit`` is injectable for tests; the
        real one skips interpreter teardown, so stdout is flushed here.)"""

        def fire():
            if not self.claim_emit():
                return
            import copy

            fallback = {"metric": "budget_exhausted", "value": 0,
                        "unit": "", "vs_baseline": None,
                        "detail": {"budget_s": self.budget_s,
                                   "budget_exhausted": True,
                                   "elapsed_s": round(self.elapsed(), 1)}}
            try:
                # snapshot: the main thread is still mutating detail (a
                # leg mid-flight); serializing the live dict could raise
                # "dictionary changed size during iteration" AFTER the
                # emit was claimed, losing the artifact entirely
                result = copy.deepcopy(holder.get("result"))
                if result is None:
                    result = fallback
                result.setdefault("detail", {})
                result["detail"].update(fallback["detail"])
                emit(result)
            except Exception:  # noqa: BLE001 — emit SOMETHING, always
                print(json.dumps(fallback))
            sys.stdout.flush()
            _exit(0)

        self._timer = threading.Timer(max(self.remaining(), 0.001), fire)
        self._timer.daemon = True
        self._timer.start()

    def disarm(self):
        if self._timer is not None:
            self._timer.cancel()


def _cpu_children_only(leg: str) -> None:
    """Refuse a leg that times CPU-pinned child processes unless this
    process runs on the CPU too.  A chip belongs to one process: a
    parent that holds it cannot hand it to children, and the children's
    CPU numbers must not ride in a TPU run's artifact."""
    import jax

    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{leg} times CPU-pinned child processes and this process "
            f"runs on '{jax.default_backend()}' — run the leg on its "
            "own with JAX_PLATFORMS=cpu")


def _time_steps(stepper, state, n_steps, repeats):
    """(min_seconds, repeat_spread) for ``n_steps`` calls of ``stepper``
    (the shared harness in benchmarks/hgcn_bench.py, which ends each
    timed window in ``block_until_ready``).  The max/min spread lets
    callers record a noisy run in the artifact."""
    from hyperspace_tpu.benchmarks.hgcn_bench import spread, time_steps_all

    times, _, _ = time_steps_all(stepper, state, n_steps, repeats)
    return min(times), spread(times)


def _poincare_steppers(cfg, pairs, plan_steps):
    """(name -> (stepper, fresh_state)) for the three update strategies:
    dense (whole-table), sparse (device unique), planned (host-planned
    indices, no device sort / unsorted scatter)."""
    import dataclasses

    from hyperspace_tpu.models import poincare_embed as pe

    out = {}
    for name, c in (("dense", cfg),
                    ("sparse", dataclasses.replace(cfg, sparse=True))):
        state, opt = pe.init_state(c)
        step_fn = pe.make_train_step(c)
        out[name] = ((lambda st, c=c, o=opt, f=step_fn: f(c, o, st, pairs)),
                     state)
    state, opt = pe.init_state(cfg)
    plan = pe.plan_sparse_steps(cfg, pairs, plan_steps, seed=0)
    # the packed variant: one row gather + ONE sorted scatter-set per step
    # regardless of optimizer moment count (docs/benchmarks.md)
    out["planned"] = (
        (lambda st, o=opt, p=plan: pe.train_step_planned_packed(cfg, o, st, p)),
        pe.pack_state(cfg, state))
    return out, plan


def _time_planned_scan(cfg, plan, repeats):
    """(wall, spread) of one scanned planned epoch (all plan rows, one
    program)."""
    from hyperspace_tpu.models import poincare_embed as pe

    state, opt = pe.init_state(cfg)
    return _time_steps(
        (lambda st, o=opt, p=plan:
         pe.train_epoch_planned_packed(cfg, o, st, p)),
        pe.pack_state(cfg, state), 1, repeats)


def bench_poincare(repeats: int = 3) -> dict:
    """Epoch time for Poincaré embeddings on a WordNet-noun-scale tree.

    Times three stepwise update strategies — dense (whole-table expmap),
    sparse (device-side unique + row scatter), and planned-packed
    (host-planned indices, one gather + one sorted scatter-set;
    `poincare_embed.train_step_planned_packed`) —
    plus the two scanned-epoch programs (`train_epoch_scan`,
    `train_epoch_planned_packed`: the whole epoch under one `lax.scan`,
    one dispatch instead of steps_per_epoch), reporting the fastest as
    the headline.  ``detail.large_table`` re-times the strategies at an
    arxiv-scale table (≥500 k rows) with riemannian_adam, where the
    per-step moment/table traffic is what the sparse path exists to
    avoid (SURVEY.md §7 hard-part #2).
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.data.wordnet import synthetic_tree
    from hyperspace_tpu.models import poincare_embed as pe

    # WordNet nouns ≈ 82k nodes / ~750k closure pairs; the synthetic stand-in
    # (depth 5, branching 9) gives 66k nodes and a comparable closure size.
    ds = synthetic_tree(depth=5, branching=9)
    cfg = pe.PoincareEmbedConfig(
        num_nodes=ds.num_nodes, dim=10, batch_size=1024, neg_samples=10
    )
    pairs = jnp.asarray(ds.pairs)
    steps_per_epoch = max(1, ds.num_pairs // cfg.batch_size)

    epochs = {}
    spreads = {}
    steppers, plan = _poincare_steppers(cfg, pairs, steps_per_epoch)
    for name, (stepper, state) in steppers.items():
        t, spreads[name] = _time_steps(stepper, state, steps_per_epoch,
                                       repeats)
        epochs[name] = round(t, 4)
    # scanned epochs: all steps_per_epoch steps as ONE XLA program
    # (`train_epoch_scan` / `train_epoch_planned_packed`) — at this table
    # size the per-step device work is tiny, so the stepwise timings above
    # are dominated by dispatch latency the scan removes
    state, opt = pe.init_state(cfg)
    t, spreads["dense_scan"] = _time_steps(
        (lambda st, o=opt: pe.train_epoch_scan(cfg, o, st, pairs,
                                               steps_per_epoch)),
        state, 1, repeats)
    epochs["dense_scan"] = round(t, 4)
    t, spreads["planned_scan"] = (  # plan reused from _poincare_steppers
        _time_planned_scan(cfg, plan, repeats))
    epochs["planned_scan"] = round(t, 4)
    update = min(epochs, key=epochs.get)

    # arxiv-scale table: dense pays O(N) table+moment traffic per step,
    # the planned path O(batch); timed per-step over a fixed step count
    big = synthetic_tree(depth=6, branching=9)
    big_cfg = pe.PoincareEmbedConfig(
        num_nodes=big.num_nodes, dim=10, batch_size=1024, neg_samples=10,
        optimizer="radam")
    big_pairs = jnp.asarray(big.pairs)
    n_big_steps = 50
    large = {"num_nodes": big.num_nodes, "optimizer": "radam"}
    big_steppers, big_plan = _poincare_steppers(big_cfg, big_pairs,
                                                n_big_steps)
    for name, (stepper, state) in big_steppers.items():
        t, _ = _time_steps(stepper, state, n_big_steps, max(2, repeats - 1))
        large[f"{name}_step_ms"] = round(t / n_big_steps * 1e3, 3)
    t, _ = _time_planned_scan(big_cfg, big_plan, max(2, repeats - 1))
    large["planned_scan_step_ms"] = round(t / n_big_steps * 1e3, 3)
    large["update"] = min(
        ("dense", "sparse", "planned", "planned_scan"),
        key=lambda n: large[f"{n}_step_ms"])

    return {
        "metric": "poincare_embed_epoch_time",
        "value": epochs[update],
        "unit": "s",
        "vs_baseline": None,
        "detail": {
            "num_nodes": ds.num_nodes,
            "num_pairs": ds.num_pairs,
            "steps_per_epoch": steps_per_epoch,
            "batch_size": cfg.batch_size,
            **{f"{k}_epoch_s": v for k, v in epochs.items()},
            "update": update,
            # max/min over the timing repeats of the winning strategy —
            # ≫1 marks a noisy run
            "repeat_spread": spreads.get(update),
            "large_table": large,
            "backend": jax.default_backend(),
        },
    }


def bench_hgcn(repeats: int = 3, dtype: str = "float32",
               agg_dtype: str = "bfloat16", use_att: bool = False,
               decoder_dtype: str | None = "bfloat16") -> dict:
    """HGCN training throughput (samples/sec/chip) on an arxiv-scale graph.

    Times ``train_step_lp``, the step ``cli.train`` runs.  Default config
    (validated quality-neutral at full 169 k-node scale over 3 seeds —
    docs/benchmarks.md quality-anchor section): f32 compute, bf16 *edge
    messages* and a bf16 decoder pass (everything accumulates f32).
    ``--decoder-dtype float32 --agg-dtype float32`` reproduces pure-f32;
    ``--dtype bfloat16`` runs everything in bf16 (faster, AUC degrades,
    opt-in); ``--use-att`` benches the attention-aggregation model.
    """
    import jax

    from hyperspace_tpu.benchmarks.hgcn_bench import run_hgcn_bench

    return run_hgcn_bench(repeats=repeats, backend=jax.default_backend(),
                          dtype=dtype, agg_dtype=agg_dtype, use_att=use_att,
                          decoder_dtype=decoder_dtype)


def bench_sampled(repeats: int = 2) -> dict:
    """Minibatch-trainer detail metric: supervised samples/s (the
    labeled-seeds-per-second unit; docs/benchmarks.md r03b)."""
    from hyperspace_tpu.benchmarks.hgcn_bench import run_sampled_bench

    return run_sampled_bench(repeats=repeats)


# the serve pipeline's stage taxonomy (docs/observability.md "Span-level
# tracing"): the first four are boundary stages — differences of
# consecutive lifecycle stamps that sum to e2e exactly by construction —
# the last two are nested engine windows inside `dispatch`
STAGE_BOUNDARY = ("queue_wait", "collate_wait", "dispatch", "serialize")
STAGE_NAMES = STAGE_BOUNDARY + ("device_compute", "rescore")


def _stage_breakdown(delta, leg: str, e2e_mean=None) -> dict:
    """Per-stage mean + p99 table from a snapshot delta's
    ``hist/serve/stage/<name>_ms`` families, with the decomposition
    invariant CHECKED: the boundary stages' means must sum to the e2e
    mean within 5 % (``e2e_mean`` overrides the delta's own e2e
    histogram when the delta window saw spans-off traffic too).  Raises
    — a silently-drifting decomposition would report a breakdown that
    no longer explains the headline latency."""
    stages: dict = {}
    for name in STAGE_NAMES:
        h = delta.get(f"hist/serve/stage/{name}_ms")
        if h and h["count"]:
            stages[name] = {"n": h["count"],
                            "mean_ms": round(h["sum"] / h["count"], 4),
                            "p99_ms": h["p99"]}
    if e2e_mean is None:
        e2e = delta.get("hist/serve/e2e_ms")
        if e2e and e2e["count"]:
            e2e_mean = e2e["sum"] / e2e["count"]
    if e2e_mean:
        total = sum(stages[s]["mean_ms"] for s in STAGE_BOUNDARY
                    if s in stages)
        ratio = total / e2e_mean
        if not 0.95 <= ratio <= 1.05:
            raise RuntimeError(
                f"{leg}: stage decomposition broke — boundary stages sum "
                f"to {total:.3f} ms vs e2e mean {e2e_mean:.3f} ms "
                f"(ratio {ratio:.3f}, want within 5%)")
        stages["e2e_mean_ms"] = round(e2e_mean, 4)
        stages["sum_vs_e2e"] = round(ratio, 4)
    return stages


def bench_serve(repeats: int = 2) -> dict:
    """Serving throughput: warm ``topk_neighbors`` queries/s per bucket.

    Builds a synthetic Poincaré table, warms one (bucket, k) executable
    per bucket of the request batcher's ladder, then times cache-miss
    batches at each bucket size (min-of-repeats; value = best bucket's
    queries/s).  Also reported: per-bucket **latency percentiles**
    (p50/p95/p99 of the ``serve/e2e_ms`` request histogram, as a DELTA
    over each bucket's timed pass alone — ``detail.latency_ms.b<N>``,
    the SLO contract numbers ROADMAP item 3 will gate on), the
    recompile count during warmup (one per bucket is the contract) and
    during the timed phase (0 is the contract — a nonzero means the
    timings include the compiler), and a cached-batcher pass over a hot
    id set whose hit/padding ratios — counter deltas over that pass
    alone, not the warmup-diluted process-cumulative gauges — land in
    the artifact (docs/benchmarks.md "serve_qps").

    Since r10 an **IVF recall leg** rides along (``detail.ivf``): a
    cluster-structured 50k table, an IVF index built on it
    (serve/index.py), per-nprobe recall@10 vs the exact engine and
    warm qps, and the contract numbers ``qps_at_recall99`` /
    ``speedup_at_recall99`` — the queries/s the approximate path
    sustains while keeping recall@10 >= 0.99, and its ratio to the
    exact scan on the same table.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hyperspace_tpu.manifolds import PoincareBall
    from hyperspace_tpu.serve.batcher import RequestBatcher
    from hyperspace_tpu.serve.engine import QueryEngine
    from hyperspace_tpu.telemetry import registry as telem

    rng = np.random.default_rng(0)
    n, dim, k = 50_000, 16, 10
    table = np.asarray(PoincareBall(1.0).expmap0(
        jnp.asarray(rng.standard_normal((n, dim)) * 0.3, jnp.float32)))
    eng = QueryEngine(table, ("poincare", 1.0))
    # cache OFF for the timed phase: every id must hit the device path
    bat = RequestBatcher(eng, min_bucket=8, max_bucket=256, cache_size=0)
    reg = telem.default_registry()
    c0 = reg.get("jax/recompiles")
    for b in bat.buckets:  # warmup: one compile per (bucket, k)
        bat.topk(rng.integers(0, n, size=b).tolist(), k)
    c1 = reg.get("jax/recompiles")
    detail = {
        "num_nodes": n, "dim": dim, "k": k, "buckets": list(bat.buckets),
        "chunk_rows": eng.chunk_rows, "scan_mode": eng.scan_mode,
        # scan precision + table dtype as executed: BENCH_r* serve_qps
        # trajectories must be comparable across precision modes
        "precision": eng.precision, "dtype": str(table.dtype),
        "recompiles_warmup": c1 - c0, "backend": jax.default_backend(),
    }
    best = 0.0
    latency = {}
    for b in bat.buckets:
        times = []
        lat_base = reg.mark()  # per-bucket latency delta window
        for _ in range(max(2, repeats)):
            ids = rng.integers(0, n, size=b).tolist()
            t0 = time.perf_counter()
            bat.topk(ids, k)
            times.append(time.perf_counter() - t0)
        qps = b / min(times)
        detail[f"qps_b{b}"] = round(qps, 1)
        best = max(best, qps)
        # p50/p95/p99 of the batcher's per-request e2e histogram over
        # THIS bucket's timed requests alone (mark/snapshot delta) —
        # the per-qps-bucket SLO numbers, sourced from hist/serve/e2e_ms.
        # "n" is the sample count behind them: at the default repeats
        # the window holds only a few requests, and a percentile with
        # its basis hidden would read as sturdier than it is
        e2e = reg.snapshot(baseline=lat_base).get("hist/serve/e2e_ms")
        if e2e:
            latency[f"b{b}"] = {
                "n": e2e["count"],
                **{q: e2e[q] for q in ("p50", "p95", "p99")}}
    detail["latency_ms"] = latency
    detail["recompiles_steady"] = reg.get("jax/recompiles") - c1
    # cache effectiveness: a cached batcher over a small hot id set.
    # The serve counters are process-cumulative and the timed phase
    # above ran cache-DISABLED, so report deltas over this pass alone
    # (registry mark/snapshot) — not the warmup-diluted globals.
    cached = RequestBatcher(eng, min_bucket=8, max_bucket=256)
    base = reg.mark()
    hot = rng.integers(0, 256, size=(8, 100))
    for row in hot:
        cached.topk(row.tolist(), k)
    delta = reg.snapshot(baseline=base)
    hits = delta.get("serve/cache_hit", 0)
    lookups = hits + delta.get("serve/cache_miss", 0)
    slots = delta.get("serve/slots", 0)
    detail["cache"] = {
        "cache_hit": hits,
        "cache_miss": delta.get("serve/cache_miss", 0),
        "cache_hit_rate": round(hits / max(lookups, 1), 4),
        "padded_waste": delta.get("serve/padded_waste", 0),
        "padded_waste_ratio": round(
            delta.get("serve/padded_waste", 0) / max(slots, 1), 4),
    }

    # --- per-stage latency decomposition (ISSUE 17): spans on for a
    # dedicated pass, mean + p99 per stage from the stage histograms
    # (``detail.stages``), and the construction invariant CHECKED at
    # bench load — the four boundary stages are differences of
    # consecutive lifecycle stamps, so their means must sum to the e2e
    # mean within 5 % (a drift means a stage boundary stopped being
    # stamped — exactly the regression this leg exists to catch)
    from hyperspace_tpu.telemetry import spans as _spans

    stage_base = reg.mark()
    _spans.enable()
    try:
        for _ in range(max(2, repeats)):
            bat.topk(rng.integers(0, n, size=64).tolist(), k)
    finally:
        _spans.disable()
    detail["stages"] = _stage_breakdown(
        reg.snapshot(baseline=stage_base), "serve_qps")

    # --- fused_vs_unfused (r12): the Pallas scan-top-k kernel
    # (scan_mode=fused, kernels/scan_topk.py — distance tiles in
    # registers, running top-k in the kernel carry) against the default
    # two-stage scan: SAME 50k table, SAME bucket ladder, paired ids.
    # Per-bucket per-mode failure degrades to a detail error (the r10
    # ivf_error pattern) instead of sinking the leg; the headline
    # serve_fused_speedup is the largest bucket's fused/two_stage qps
    # ratio (where the fused kernel matters most).  On CPU both run XLA
    # (the fused path is the kernel's twin) — the ratio there tracks
    # the twin's merge loop, not the TPU win (docs/benchmarks.md r12).
    def _fused_leg():
        out = {"k": k, "buckets": {}}
        engines = {}
        for m in ("two_stage", "fused"):
            engines[m] = QueryEngine(table, ("poincare", 1.0), scan_mode=m)
        out["chunk_rows"] = {m: e.chunk_rows for m, e in engines.items()}
        for b in bat.buckets:
            ids = rng.integers(0, n, size=b).astype(np.int32)
            row = {}
            for m, e in engines.items():
                try:
                    _, dd = e.topk_neighbors(ids, k)  # compile + warm
                    jax.device_get(dd)
                    ts = []
                    for _ in range(max(2, repeats)):
                        t0 = time.perf_counter()
                        _, dd = e.topk_neighbors(ids, k)
                        jax.device_get(dd)
                        ts.append(time.perf_counter() - t0)
                    row[m] = round(b / min(ts), 1)
                except Exception as err:  # noqa: BLE001 — one mode
                    # failing must not discard the other mode's reading
                    # or the remaining buckets; the deadline _LegTimeout
                    # is a BaseException and still flies through
                    row[f"{m}_error"] = repr(err)
            if row.get("two_stage") and row.get("fused"):
                row["ratio"] = round(row["fused"] / row["two_stage"], 3)
            out["buckets"][f"b{b}"] = row
        # the headline is pinned to the LARGEST bucket (where the fused
        # kernel matters most) and says so — a failed largest bucket
        # leaves it absent rather than silently substituting another
        # bucket's ratio into the gated trend
        top = bat.buckets[-1]
        ratio = out["buckets"][f"b{top}"].get("ratio")
        if ratio is not None:
            out["serve_fused_speedup"] = ratio
            out["speedup_bucket"] = top
        return out

    try:
        detail["fused_vs_unfused"] = _fused_leg()
    except Exception as e:  # noqa: BLE001 — the fused A/B must not
        # sink the serve_qps reading (the deadline _LegTimeout is a
        # BaseException and still flies through)
        detail["fused_error"] = repr(e)

    # --- IVF recall leg (r10): recall@10 vs the exact engine per
    # nprobe, and the headline **qps at recall@10 >= 0.99** (ROADMAP
    # item 2's contract).  The table here is CLUSTER-STRUCTURED (512
    # Poincaré clusters at moderate radii) — the structure real
    # embedding tables have (trees/communities), and the regime an IVF
    # index is for; an isotropic blob admits no sub-linear index by
    # construction (docs/benchmarks.md r10).
    def _ivf_leg():
        from hyperspace_tpu.serve.index import build_index

        ncl, ncells = 512, 192
        centers = rng.standard_normal((ncl, dim)) * 0.25
        vv = (centers[rng.integers(0, ncl, size=n)]
              + rng.standard_normal((n, dim)) * 0.05)
        ctable = np.asarray(PoincareBall(1.0).expmap0(
            jnp.asarray(vv, jnp.float32)))
        ids = rng.integers(0, n, size=256).astype(np.int32)

        def timed_qps(e):
            _, dd = e.topk_neighbors(ids, k)  # compile + warm
            jax.device_get(dd)
            ts = []
            for _ in range(max(2, repeats)):
                t0 = time.perf_counter()
                _, dd = e.topk_neighbors(ids, k)
                jax.device_get(dd)
                ts.append(time.perf_counter() - t0)
            return len(ids) / min(ts)

        ex = QueryEngine(ctable, ("poincare", 1.0))
        exact_qps = timed_qps(ex)
        ei, _ = (np.asarray(a) for a in ex.topk_neighbors(ids, k))
        t0 = time.perf_counter()
        idx = build_index(ctable, ("poincare", 1.0), ncells, iters=8,
                          seed=0, balance=3.0)
        out = {"table": "clustered", "ncells": ncells,
               "max_cell": idx.max_cell,
               "build_s": round(time.perf_counter() - t0, 2),
               "exact_qps": round(exact_qps, 1), "probes": {}}
        qps_at = 0.0
        for npb in (1, 2, 4, 8):
            try:
                e = QueryEngine(ctable, ("poincare", 1.0), index=idx,
                                nprobe=npb)
                ii, _ = (np.asarray(a) for a in e.topk_neighbors(ids, k))
                rec = float(np.mean([len(set(ei[j]) & set(ii[j])) / k
                                     for j in range(len(ids))]))
                qps = timed_qps(e)
            except Exception as e:  # noqa: BLE001 — one probe setting
                # failing (e.g. an under-filled low-nprobe probe on an
                # unlucky platform/seed) must not discard the baseline
                # and the other probes' already-measured rows; the
                # deadline _LegTimeout is a BaseException and still
                # flies through
                out["probes"][f"np{npb}"] = {"error": repr(e)}
                continue
            out["probes"][f"np{npb}"] = {"recall10": round(rec, 4),
                                         "qps": round(qps, 1)}
            if rec >= 0.99:
                qps_at = max(qps_at, qps)
        # the headline pair: best qps among probe settings that keep
        # recall@10 >= 0.99, and its ratio to the exact scan (> 1 means
        # the index pays for itself at production-grade recall)
        out["qps_at_recall99"] = round(qps_at, 1)
        out["speedup_at_recall99"] = round(qps_at / max(exact_qps, 1e-9), 2)
        return out

    try:
        detail["ivf"] = _ivf_leg()
    except Exception as e:  # noqa: BLE001 — the recall leg must not
        # sink the serve_qps reading (the deadline _LegTimeout is a
        # BaseException and still flies through)
        detail["ivf_error"] = repr(e)
    return {"metric": "serve_qps", "value": round(best, 1),
            "unit": "queries/s", "vs_baseline": None, "detail": detail}


def bench_cold_start(repeats: int = 1) -> dict:
    """Cold start → time-to-first-query, as REAL subprocess restarts
    (docs/benchmarks.md r14).

    The serve stack's cold-start cost is compile time: every (bucket,
    k) executable is built on first hit, so a fresh process's first
    query pays XLA (and a cold bucket's first hit pays it again at
    p99).  This leg measures the whole pillar stack end-to-end — spawn
    ``cli.serve serve`` (the stdin JSONL loop) against a small
    artifact, stamp ``spawn → first topk response`` wall-clock
    (``ttfq_ms``), then hold the bucket and read the stats
    ``recompiles`` counter — under three restart regimes:

    - ``cache_off``: persistent compilation cache disabled — the
      historical behavior, every restart recompiles everything;
    - ``warm_cache``: second process over a pre-populated
      ``compile_cache_dir`` — the first query deserializes its
      executable instead of compiling;
    - ``warm_prewarm``: warm cache + ``prewarm=1`` — the whole ladder
      is deserialized BEFORE the first line is read, so the first query
      on ANY bucket is warm (``recompiles_steady`` 0 is the contract).

    Value = the ``warm_prewarm`` ttfq (ms); the regime deltas are the
    pillar's measured win.  The children are pinned to the CPU, the leg
    is labelled ``cpu`` and refuses to run in a process on another
    backend (:func:`_cpu_children_only`): process spawn + the jax
    import dominate ttfq there, so the cache's effect reads in the
    ``recompiles_first`` column and the off-vs-warm delta, not in the
    import constant.  The cache the leg fills lives at the fixed
    ``<repo>/.cache/bench_cold_start`` and is emptied first; the
    children are handed it by flag, so the leg needs
    ``JAX_COMPILATION_CACHE_DIR`` unset (they refuse a second
    directory).
    """
    import shutil
    import subprocess
    import tempfile

    import jax.numpy as jnp
    import numpy as np

    from hyperspace_tpu.manifolds import PoincareBall

    _cpu_children_only("bench_cold_start")

    n, dim, k = 4096, 8, 5
    rng = np.random.default_rng(0)
    table = np.asarray(PoincareBall(1.0).expmap0(
        jnp.asarray(rng.standard_normal((n, dim)) * 0.3, jnp.float32)))

    def run_once(art: str, cache: str, prewarm: bool,
                 queries: int = 3) -> dict:
        args = [sys.executable, "-m", "hyperspace_tpu.cli.serve", "serve",
                f"artifact={art}", f"compile_cache_dir={cache}",
                f"prewarm={'1' if prewarm else '0'}", f"k={k}",
                "max_bucket=64"]
        # the leg's subject is restart + cache mechanics, which the
        # CPU path exercises end-to-end
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        t0 = time.perf_counter()
        proc = subprocess.Popen(args, stdin=subprocess.PIPE,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.DEVNULL, text=True,
                                env=env,
                                cwd=os.path.dirname(os.path.abspath(__file__)))
        try:
            def ask(req: dict) -> dict:
                proc.stdin.write(json.dumps(req) + "\n")
                proc.stdin.flush()
                line = proc.stdout.readline()
                if not line:
                    raise RuntimeError(
                        f"serve subprocess died rc={proc.poll()}")
                return json.loads(line)

            first = ask({"op": "topk", "ids": [0, 1, 2], "k": k})
            ttfq = time.perf_counter() - t0
            if "error" in first:
                raise RuntimeError(f"first query failed: {first}")
            r1 = ask({"op": "stats"})["recompiles"]
            for i in range(queries):  # same bucket, fresh ids: steady state
                ask({"op": "topk", "ids": [3 * i + 3, 3 * i + 4, 3 * i + 5],
                     "k": k})
            r2 = ask({"op": "stats"})["recompiles"]
            proc.stdin.close()
            proc.wait(timeout=60)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        return {"ttfq_ms": round(ttfq * 1e3, 1),
                "recompiles_first": r1,
                "recompiles_steady": r2 - r1}

    detail: dict = {"num_nodes": n, "dim": dim, "k": k,
                    "backend": "cpu"}  # the children's, by construction
    cache = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".cache", "bench_cold_start")
    shutil.rmtree(cache, ignore_errors=True)  # cache_cold_prime is cold
    with tempfile.TemporaryDirectory() as tmp:
        from hyperspace_tpu.serve import export_artifact

        art = os.path.join(tmp, "artifact")
        export_artifact(art, table, ("poincare", 1.0),
                        model_config={"c": 1.0})
        detail["cache_off"] = run_once(art, "0", prewarm=False)
        # priming run: prewarm=1 walks the WHOLE ladder, so every bucket
        # executable lands in the persistent cache for the runs below
        detail["cache_cold_prime"] = run_once(art, cache, prewarm=True)
        detail["warm_cache"] = run_once(art, cache, prewarm=False)
        detail["warm_prewarm"] = run_once(art, cache, prewarm=True)
    value = detail["warm_prewarm"]["ttfq_ms"]
    # duplicated under unambiguous names so the compact-field paths work
    # in BOTH auto mode (nested under detail.cold_start) and headline
    # mode (flat detail) — a flat "recompiles_steady" path would also
    # match the serve/serve_http headline details and mislabel them
    detail["cold_ttfq_ms"] = value
    detail["recompiles_steady"] = detail["warm_prewarm"]["recompiles_steady"]
    detail["cold_recompiles_steady"] = detail["recompiles_steady"]
    return {"metric": "cold_ttfq_ms", "value": value, "unit": "ms",
            "vs_baseline": None, "detail": detail}


def open_loop_arrivals(n: int, qps: float, mode: str = "poisson",
                       seed: int = 0):
    """Arrival offsets (seconds from start) for ``n`` requests at a
    fixed OFFERED rate of ``qps`` — the open-loop load model: arrivals
    are scheduled by the clock, never by the previous response, so a
    slow server accumulates queueing instead of silently throttling the
    load (the closed-loop blind spot; docs/benchmarks.md r13).
    ``mode="poisson"`` draws i.i.d. exponential gaps (memoryless
    arrivals — the production-traffic null model); ``"even"`` spaces
    them exactly 1/qps apart (deterministic, for A/B noise control)."""
    import numpy as np

    if n <= 0 or qps <= 0:
        raise ValueError(f"need n > 0 and qps > 0; got n={n} qps={qps}")
    if mode == "even":
        return np.arange(n) / qps
    if mode != "poisson":
        raise ValueError(f"arrivals mode {mode!r} (want poisson|even)")
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / qps, size=n))


def bench_serve_http(repeats: int = 2, *, qps: float = 120.0,
                     duration_s: float = 2.0, table_rows: int = 20_000,
                     arrivals: str = "poisson",
                     overload_qps: float = 1200.0,
                     overload_s: float = 0.8) -> dict:
    """HTTP front-door latency at FIXED OFFERED LOAD (docs/serving.md
    "HTTP front door", docs/benchmarks.md r13).

    Starts the asyncio server (serve/server.py) over a continuous-
    batching collator in-process, warms every bucket executable
    closed-loop, then drives an **open-loop generator** (fixed offered
    qps, Poisson or evenly-spaced arrivals, one in-process asyncio
    client connection per request) through ``POST /v1/topk``:

    - ``repeats`` passes per request-size class (1 / 16 / 64 ids — the
      b8/b16/b64 rungs they pad to when alone), each class reporting
      p50/p95/p99 of ``serve/e2e_ms`` as a registry mark/snapshot DELTA
      over its passes (``detail.latency_ms.b<N>``; more repeats = more
      samples behind the percentiles, the open-loop analog of
      min-of-N), plus the aggregate distribution across all passes —
      ``http_p99_ms``, the compact headline;
    - ``recompiles_steady`` over the timed passes (0 is the contract —
      the warmup covers the ladder, so collation can never hand the
      compiler a fresh shape mid-leg);
    - an **overload pass**: offered load far past capacity into a
      ``queue_max=8`` bounded batcher — every request is answered and
      the excess sheds with HTTP 429 (``shed_rate``), never unbounded
      queueing.

    Value = the aggregate p99 (ms) at the configured offered load.
    CPU readings are wall-clock noisy; the shed/recompile columns are
    the stable contract rows.
    """
    import asyncio

    import jax
    import jax.numpy as jnp
    import numpy as np

    from hyperspace_tpu.manifolds import PoincareBall
    from hyperspace_tpu.serve.batcher import RequestBatcher, bucket_for
    from hyperspace_tpu.serve.engine import QueryEngine
    from hyperspace_tpu.serve.server import HttpFrontDoor
    from hyperspace_tpu.telemetry import registry as telem

    rng = np.random.default_rng(0)
    n, dim, k = table_rows, 16, 10
    table = np.asarray(PoincareBall(1.0).expmap0(
        jnp.asarray(rng.standard_normal((n, dim)) * 0.3, jnp.float32)))
    eng = QueryEngine(table, ("poincare", 1.0))
    # cache OFF so every request exercises the collated device path;
    # admission bound generous — the timed passes must not shed
    bat = RequestBatcher(eng, min_bucket=8, max_bucket=64, cache_size=0,
                         queue_max=256)
    reg = telem.default_registry()

    async def _post(host, port, payload):
        reader, writer = await asyncio.open_connection(host, port)
        try:
            body = json.dumps(payload).encode("utf-8")
            writer.write(
                (f"POST /v1/topk HTTP/1.1\r\nHost: bench\r\n"
                 f"Content-Length: {len(body)}\r\n"
                 "Connection: close\r\n\r\n").encode("latin-1") + body)
            await writer.drain()
            data = await reader.read()
        finally:
            writer.close()
        head, _, _body = data.partition(b"\r\n\r\n")
        return int(head.split(None, 2)[1])

    async def _open_loop(host, port, sizes, pass_qps, n_req, seed):
        """Fire n_req requests of ``sizes``-id batches at pass_qps;
        returns {status: count}.  Arrival times come from the clock
        (open loop), not from responses."""
        offsets = open_loop_arrivals(n_req, pass_qps, arrivals, seed)
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        tasks = []
        for off in offsets:
            delay = t0 + float(off) - loop.time()
            if delay > 0:
                await asyncio.sleep(delay)
            ids = rng.integers(0, n, size=sizes).tolist()
            tasks.append(asyncio.ensure_future(
                _post(host, port, {"ids": ids, "k": k})))
        results = await asyncio.gather(*tasks, return_exceptions=True)
        statuses: dict = {}
        for r in results:
            key = (f"error:{type(r).__name__}"
                   if isinstance(r, BaseException) else str(int(r)))
            statuses[key] = statuses.get(key, 0) + 1
        return statuses

    def _percentiles(delta):
        e2e = delta.get("hist/serve/e2e_ms")
        if not e2e:
            return None
        return {"n": e2e["count"],
                **{q: e2e[q] for q in ("p50", "p95", "p99")}}

    async def _run():
        detail = {
            "num_nodes": n, "dim": dim, "k": k,
            "buckets": list(bat.buckets), "offered_qps": qps,
            "arrivals": arrivals, "duration_s": duration_s,
            "backend": jax.default_backend(),
        }
        door = HttpFrontDoor(bat, max_wait_us=2000)
        await door.start()
        c0 = reg.get("jax/recompiles")
        # closed-loop warmup: one compile per (bucket, k) — every rung
        # of the ladder, so collation can never surface a cold shape
        # during the timed passes
        for b in bat.buckets:
            await _post(door.host, door.port,
                        {"ids": rng.integers(0, n, size=b).tolist(),
                         "k": k})
        c1 = reg.get("jax/recompiles")
        detail["recompiles_warmup"] = c1 - c0

        latency = {}
        agg_base = reg.mark()
        n_req = max(8, int(qps * duration_s))
        # one size class per ladder region: single-id (the continuous-
        # batching regime — collation forms its buckets), a mid bucket,
        # and the top bucket; each pads to a DISTINCT rung when alone.
        # ``repeats`` open-loop passes per class widen the sample count
        # behind the percentiles (the open-loop analog of min-of-N).
        for si, size in enumerate((1, 16, 64)):
            pass_base = reg.mark()
            statuses: dict = {}
            for rep in range(max(1, repeats)):
                got = await _open_loop(door.host, door.port, size, qps,
                                       n_req, 16 * si + rep)
                for key, v in got.items():
                    statuses[key] = statuses.get(key, 0) + v
            row = _percentiles(reg.snapshot(baseline=pass_base)) or {}
            row["statuses"] = statuses
            latency[f"b{bucket_for(size, bat.buckets)}"] = row
        detail["latency_ms"] = latency
        agg = _percentiles(reg.snapshot(baseline=agg_base))
        if agg is None:
            # no request observed a latency = none succeeded: the leg
            # FAILED — never emit p99=0, which the lower-is-better
            # trend gate would read as the best round ever
            await door.drain()
            raise RuntimeError(
                "serve_http: no successful timed request — statuses "
                f"{ {k: v['statuses'] for k, v in latency.items()} }")
        detail["aggregate_ms"] = agg
        detail["http_p99_ms"] = agg["p99"]
        detail["recompiles_steady"] = reg.get("jax/recompiles") - c1

        # observability-overhead pairs: the SAME shapes with the access
        # log + SLO window + SPAN LAYER armed vs off — the "~free when
        # on" contract (docs/observability.md; the span layer's budget
        # is <= 1.05x, ISSUE 17).  Order is BALANCED (off,on,on,off)
        # and each mode takes its min-of-N p99: on a noisy CPU host
        # whichever pass runs first in a pair reads slower for reasons
        # that have nothing to do with instrumentation (measured 0.4–
        # 2.6× swings with the order reversed) — min-of-N per mode is
        # the repo's standard noise treatment, applied per mode here
        import tempfile

        from hyperspace_tpu.serve.access import AccessLog
        from hyperspace_tpu.telemetry import spans as _spans
        from hyperspace_tpu.telemetry.window import SloWindow

        obs_n = max(8, n_req // 2)
        obs_dir = tempfile.mkdtemp(prefix="bench_obs_")
        alog = AccessLog(os.path.join(obs_dir, "access.jsonl"))
        p99s: dict = {"off": [], "on": []}
        stage_base = reg.mark()  # only on-passes feed stage histograms
        on_e2e_sum = 0.0
        on_e2e_n = 0
        try:
            for i, mode in enumerate(("off", "on", "on", "off")):
                if mode == "on":
                    bat.access_sink = alog.emit
                    bat.window = SloWindow(30.0)
                    _spans.enable()
                pass_base = reg.mark()
                await _open_loop(door.host, door.port, 16, qps, obs_n,
                                 40 + i)
                pass_delta = reg.snapshot(baseline=pass_base)
                row = _percentiles(pass_delta)
                _spans.disable()
                bat.access_sink = None
                bat.window = None
                if mode == "on":
                    # the on-passes' own e2e basis for the stage-sum
                    # check (the stage window below spans off-passes
                    # whose e2e carries no stage samples)
                    e2e = pass_delta.get("hist/serve/e2e_ms")
                    if e2e and e2e["count"]:
                        on_e2e_sum += e2e["sum"]
                        on_e2e_n += e2e["count"]
                if row:
                    p99s[mode].append(row["p99"])
        finally:
            _spans.disable()
            bat.access_sink = None
            bat.window = None
            alog.close()
            import shutil

            shutil.rmtree(obs_dir, ignore_errors=True)
        if p99s["off"] and p99s["on"] and min(p99s["off"]):
            off_p99, on_p99 = min(p99s["off"]), min(p99s["on"])
            detail["observability"] = {
                "requests_per_pass": obs_n,
                "p99_off_ms": off_p99, "p99_on_ms": on_p99,
                "p99_pairs": p99s,
                "access_lines": alog.lines,
                "overhead_ratio": round(on_p99 / off_p99, 4),
            }
        else:
            detail["observability"] = {"error": "paired pass empty",
                                       "pairs": p99s}
        # the per-stage breakdown beside http_p99_ms (ISSUE 17): mean +
        # p99 per stage over the spans-on passes, with the boundary-sum
        # == e2e invariant checked against those passes' own e2e mean
        detail["stages"] = _stage_breakdown(
            reg.snapshot(baseline=stage_base), "serve_http",
            e2e_mean=(on_e2e_sum / on_e2e_n if on_e2e_n else None))
        await door.drain()

        # overload pass: offered load far past capacity into a small
        # bounded queue — the excess must shed with HTTP 429 (never
        # queue unboundedly) and EVERY request must still be answered
        obat = RequestBatcher(eng, min_bucket=8, max_bucket=64,
                              cache_size=0, queue_max=8,
                              deadline_ms=1000.0, ladder_down_after=3)
        odoor = HttpFrontDoor(obat, max_wait_us=2000)
        await odoor.start()
        offered = max(16, int(overload_qps * overload_s))
        statuses = await _open_loop(odoor.host, odoor.port, 1,
                                    overload_qps, offered, 99)
        await odoor.drain()
        answered = sum(v for s, v in statuses.items()
                       if not s.startswith("error"))
        shed = statuses.get("429", 0)
        detail["overload"] = {
            "offered": offered, "offered_qps": overload_qps,
            "queue_max": 8, "statuses": statuses,
            "answered": answered,
            "shed": shed,
            "deadline_exceeded": statuses.get("504", 0),
        }
        detail["shed_rate"] = round(shed / offered, 3)
        detail["deadline_rate"] = round(
            statuses.get("504", 0) / offered, 3)
        return detail

    detail = asyncio.run(_run())
    return {"metric": "serve_http_p99_ms", "value": detail["http_p99_ms"],
            "unit": "ms", "vs_baseline": None, "detail": detail}


def bench_live_index(repeats: int = 1, *, qps: float = 80.0,
                     duration_s: float = 3.0,
                     table_rows: int = 6_000) -> dict:
    """Live mutable index under sustained load (docs/serving.md "Live
    index and rollover", ISSUE 18).

    One in-process HTTP front door over a :class:`LiveQueryEngine`
    (serve/delta.py) with the rollover coordinator armed
    (serve/rollover.py), driven through three phases:

    - **freshness**: serialized insert → query-by-the-new-id probes
      (each inserted vector is a near-duplicate of a known anchor row,
      so the probe's top-1 is checkable), then deletes with
      must-not-return probes, then one explicit compaction —
      ``upsert_visible_ms`` is the enqueue→applied histogram the
      batcher's mutation envelope observes (PR 15 machinery);
    - **steady + rollover**: an open-loop query stream at fixed offered
      qps CONCURRENT with a continuous upsert stream and sequential
      staleness probes (upsert a near-duplicate, immediately query it
      through the result cache — the generation-folded scan signature
      must make the pre-mutation cache rows unreachable), with a full
      blue-green rollover fired mid-stream; ``p99_during_rollover_ms``
      is the e2e delta over the rollover span, and the steady-state
      recompile counters are split pre-roll / rollover / post-flip
      (the contract: 0 outside the rollover's own standby build);
    - **oracle**: final live answers vs a frozen engine rebuilt from
      scratch over the final master table (deleted ids host-filtered
      from an overfetched oracle top-k) — ``recall_vs_oracle``.

    Value = the aggregate e2e p99 (ms) over the concurrent phase.  The
    contract columns are ``errors`` / ``stale_results`` /
    ``recompiles_steady`` — all must be 0 (``live_ok``).
    """
    import asyncio

    import jax
    import jax.numpy as jnp
    import numpy as np

    from hyperspace_tpu.manifolds import PoincareBall
    from hyperspace_tpu.parallel.host_table import HostEmbedTable
    from hyperspace_tpu.serve.batcher import RequestBatcher
    from hyperspace_tpu.serve.delta import LiveQueryEngine
    from hyperspace_tpu.serve.engine import QueryEngine
    from hyperspace_tpu.serve.rollover import RolloverCoordinator
    from hyperspace_tpu.serve.server import HttpFrontDoor
    from hyperspace_tpu.telemetry import registry as telem

    rng = np.random.default_rng(7)
    n, dim, k, cap = table_rows, 16, 10, 512
    spec = ("poincare", 1.0)
    base_arr = np.asarray(PoincareBall(1.0).expmap0(
        jnp.asarray(rng.standard_normal((n, dim)) * 0.3, jnp.float32)))

    def _make_batcher(arr):
        live = LiveQueryEngine(
            QueryEngine(np.array(arr), spec),
            HostEmbedTable.from_array(np.array(arr)),
            capacity=cap, auto_compact=False)
        # cache ON on purpose: the staleness probes below are only a
        # proof if a stale cache row COULD have answered them
        return live, RequestBatcher(live, min_bucket=8, max_bucket=64,
                                    cache_size=4096, queue_max=256)

    live, bat = _make_batcher(base_arr)
    reg = telem.default_registry()
    deleted_ids: set = set()
    # disjoint id pools so concurrent writers never collide: the random
    # update stream, the probe ids (rewritten to near-duplicates of...)
    # and the probe TARGET anchors (...which must stay untouched)
    update_pool = rng.permutation(n)[:128].tolist()
    probe_pool = [int(i) for i in range(n) if i not in set(update_pool)]
    probe_ids, anchor_ids = probe_pool[:200], probe_pool[200:400]

    async def _http(host, port, method, path, payload=None):
        reader, writer = await asyncio.open_connection(host, port)
        try:
            body = (b"" if payload is None
                    else json.dumps(payload).encode("utf-8"))
            writer.write(
                (f"{method} {path} HTTP/1.1\r\nHost: bench\r\n"
                 f"Content-Length: {len(body)}\r\n"
                 "Connection: close\r\n\r\n").encode("latin-1") + body)
            await writer.drain()
            data = await reader.read()
        finally:
            writer.close()
        head, _, rbody = data.partition(b"\r\n\r\n")
        try:
            parsed = json.loads(rbody.decode("utf-8"))
        except ValueError:
            parsed = None
        return int(head.split(None, 2)[1]), parsed

    def _percentiles(delta, name="hist/serve/e2e_ms"):
        h = delta.get(name)
        if not h:
            return None
        return {"n": h["count"], **{q: h[q] for q in ("p50", "p95", "p99")}}

    async def _run():
        detail = {
            "num_nodes": n, "dim": dim, "k": k, "delta_cap": cap,
            "offered_qps": qps, "duration_s": duration_s,
            "backend": jax.default_backend(),
        }
        door = HttpFrontDoor(bat, max_wait_us=2000)

        def standby_builder(target):
            # in-process blue-green: the standby is rebuilt from the
            # CURRENT live master (write-through makes it the truth) and
            # the known tombstones are re-applied before the flip gate
            cur = door.batcher.engine
            live2, bat2 = _make_batcher(cur.master.to_array())
            if deleted_ids:
                live2.delete(sorted(deleted_ids))
            return bat2

        door.rollover = RolloverCoordinator(door, standby_builder,
                                            prewarm_ks=(k,))
        await door.start()
        host, port = door.host, door.port
        c0 = reg.get("jax/recompiles")
        # warm the whole ladder through the LIVE path (base scan with
        # the traced drop mask + the delta-segment scan per bucket)
        for b in bat.buckets:
            await _http(host, port, "POST", "/v1/topk",
                        {"ids": rng.integers(0, n, size=b).tolist(),
                         "k": k})
        detail["recompiles_warmup"] = reg.get("jax/recompiles") - c0

        stale = errors = 0
        next_id = n

        # --- phase 1: freshness (serialized insert/delete probes) -----
        ins_n, del_m = 8 * max(1, repeats), 4 * max(1, repeats)
        fresh_base = reg.mark()
        inserted = []
        for i in range(ins_n):
            anchor = int(anchor_ids[-(i + 1)])
            vec = base_arr[anchor] + rng.normal(0, 1e-4, dim)
            s, _r = await _http(host, port, "POST", "/v1/upsert",
                                {"ids": [next_id],
                                 "rows": [vec.tolist()]})
            errors += s != 200
            s, r = await _http(host, port, "POST", "/v1/topk",
                               {"ids": [next_id], "k": k})
            if s != 200:
                errors += 1
            elif r["neighbors"][0][0] != anchor:
                stale += 1  # the new row's nearest MUST be its anchor
            inserted.append(next_id)
            next_id += 1
        for di, gone in enumerate(inserted[:del_m]):
            s, _r = await _http(host, port, "POST", "/v1/delete",
                                {"ids": [gone]})
            errors += s != 200
            # query the tombstone's OWN anchor: the near-duplicate
            # would be its top-1 if any stale row could still answer
            s, r = await _http(host, port, "POST", "/v1/topk",
                               {"ids": [int(anchor_ids[-(di + 1)])],
                                "k": k})
            if s != 200:
                errors += 1
            elif gone in r["neighbors"][0]:
                stale += 1
            deleted_ids.add(gone)
        detail["freshness"] = {
            "inserted": ins_n, "deleted": del_m,
            "upsert_visible_ms": _percentiles(
                reg.snapshot(baseline=fresh_base),
                "hist/serve/upsert_visible_ms"),
        }
        # one explicit compaction (auto_compact stays off so the timed
        # phase below cannot hide a compile in a background thread);
        # the re-clustered base is a NEW table shape — re-warm it and
        # book those compiles to the compaction, not to steady state
        c_pre = reg.get("jax/recompiles")
        detail["compaction"] = live.compact()
        for b in bat.buckets:
            await _http(host, port, "POST", "/v1/topk",
                        {"ids": rng.integers(0, n, size=b).tolist(),
                         "k": k})
        detail["recompiles_compaction"] = reg.get("jax/recompiles") - c_pre

        # --- phase 2: steady load + mid-stream blue-green rollover ----
        h0 = (await _http(host, port, "GET", "/healthz"))[1]
        stop = asyncio.Event()
        pause = asyncio.Event()
        probe_lock = asyncio.Lock()
        statuses: dict = {}

        async def query_stream():
            n_req = max(16, int(qps * duration_s))
            offsets = open_loop_arrivals(n_req, qps, "poisson", 3)
            loop = asyncio.get_running_loop()
            t0 = loop.time()
            tasks = []
            for off in offsets:
                delay = t0 + float(off) - loop.time()
                if delay > 0:
                    await asyncio.sleep(delay)
                ids = rng.integers(0, n, size=4).tolist()
                tasks.append(asyncio.ensure_future(
                    _http(host, port, "POST", "/v1/topk",
                          {"ids": ids, "k": k})))
            for s, _r in await asyncio.gather(*tasks):
                statuses[str(s)] = statuses.get(str(s), 0) + 1

        async def update_stream():
            i = 0
            while not stop.is_set():
                uid = int(update_pool[i % len(update_pool)])
                # pure-numpy ball point: the steady phase must not run
                # ANY fresh jax op (its tiny one-time compiles would
                # read as steady-state recompile pollution)
                g = rng.standard_normal(dim) * 0.3
                vec = g / (1.0 + float(np.linalg.norm(g)))
                s, _r = await _http(host, port, "POST", "/v1/upsert",
                                    {"ids": [uid],
                                     "rows": [vec.tolist()]})
                statuses[str(s)] = statuses.get(str(s), 0) + 1
                i += 1
                await asyncio.sleep(1.0 / max(qps / 5.0, 1.0))

        probe_stats = {"probes": 0}

        async def probe_stream():
            nonlocal stale, errors
            i = 0
            while not stop.is_set():
                if pause.is_set():
                    await asyncio.sleep(0.05)
                    continue
                async with probe_lock:
                    p = int(probe_ids[i % len(probe_ids)])
                    q = int(anchor_ids[i % (len(anchor_ids) - ins_n)])
                    vec = base_arr[q] + rng.normal(0, 1e-4, dim)
                    s1, _r = await _http(host, port, "POST", "/v1/upsert",
                                         {"ids": [p],
                                          "rows": [vec.tolist()]})
                    s2, r = await _http(host, port, "POST", "/v1/topk",
                                        {"ids": [p], "k": k})
                    if s1 != 200 or s2 != 200:
                        errors += 1
                    elif r["neighbors"][0][0] != q:
                        stale += 1  # a cached pre-mutation row answered
                    probe_stats["probes"] += 1
                i += 1
                await asyncio.sleep(0.1)

        steady_base = reg.mark()
        c_steady0 = reg.get("jax/recompiles")
        qtask = asyncio.ensure_future(query_stream())
        utask = asyncio.ensure_future(update_stream())
        ptask = asyncio.ensure_future(probe_stream())
        await asyncio.sleep(duration_s * 0.35)
        # quiesce the probes (an upsert→verify pair must not straddle
        # the flip: its write would land on the outgoing engine), then
        # roll over mid-stream with queries + updates still flowing
        async with probe_lock:
            pause.set()
        c_roll0 = reg.get("jax/recompiles")
        roll_base = reg.mark()
        t_roll = time.perf_counter()
        s, flip = await _http(host, port, "POST", "/admin/rollover",
                              {"target": "inproc-standby"})
        roll_s = time.perf_counter() - t_roll
        errors += s != 200
        detail["p99_during_rollover_ms"] = (_percentiles(
            reg.snapshot(baseline=roll_base)) or {}).get("p99")
        c_flip = reg.get("jax/recompiles")
        pause.clear()
        await qtask
        stop.set()
        await asyncio.gather(utask, ptask)
        h1 = (await _http(host, port, "GET", "/healthz"))[1]
        agg = _percentiles(reg.snapshot(baseline=steady_base))
        if agg is None:
            await door.drain()
            raise RuntimeError(
                f"live_index: no successful timed request — {statuses}")
        detail["aggregate_ms"] = agg
        detail["live_p99_ms"] = agg["p99"]
        detail["achieved_qps"] = round(agg["n"] / duration_s, 1)
        detail["statuses"] = statuses
        detail["staleness_probes"] = probe_stats["probes"]
        errors += sum(v for key, v in statuses.items() if key != "200")
        detail["rollover"] = {
            "flip": flip, "seconds": round(roll_s, 3),
            "fingerprint_changed": h0["fingerprint"] != h1["fingerprint"],
        }
        detail["recompiles_preroll"] = c_roll0 - c_steady0
        detail["recompiles_rollover"] = c_flip - c_roll0
        detail["recompiles_steady"] = (reg.get("jax/recompiles") - c_flip
                                       + detail["recompiles_preroll"])
        await door.drain()

        # --- phase 3: recall vs a rebuilt-from-scratch frozen oracle --
        cur = door.batcher.engine
        arr = cur.master.to_array()
        oracle = QueryEngine(np.array(arr), spec)
        probe = rng.permutation(n)[:48].astype(np.int64)
        li, _ld = cur.topk_neighbors(probe, k)
        oi, _od = oracle.topk_neighbors(
            probe, k + len(deleted_ids), exclude_self=True)
        oi = np.asarray(oi)
        hits = 0
        for row in range(probe.size):
            want = [j for j in oi[row].tolist()
                    if j not in deleted_ids][:k]
            hits += len(set(np.asarray(li)[row].tolist()) & set(want))
        detail["recall_vs_oracle"] = round(hits / (probe.size * k), 4)
        detail["errors"] = errors
        detail["stale_results"] = stale
        detail["live_ok"] = (errors == 0 and stale == 0
                             and detail["recompiles_steady"] == 0
                             and detail["recall_vs_oracle"] >= 0.99)
        return detail

    detail = asyncio.run(_run())
    return {"metric": "live_index_p99_ms", "value": detail["live_p99_ms"],
            "unit": "ms", "vs_baseline": None, "detail": detail}


def bench_resilience(repeats: int = 1) -> dict:
    """Chaos recovery + overload shedding (docs/resilience.md).

    Two sub-legs, both assertions-as-measurements — the artifact rows
    ARE the acceptance evidence the chaos suite gates on:

    - **chaos_train**: a tiny Poincaré run with one seeded NaN fault
      (``train.step_nan``) under ``rollback=2`` — recovery means the
      run completes its full step budget with a finite loss and
      EXACTLY ONE rollback; the row records both.
    - **overload**: a bounded-queue batcher (``queue_max=4``,
      ``deadline_ms=250``) hammered by 16 concurrent threads — the
      shed-rate column, the degradation ladder's peak level and
      whether it recovered (hysteresis observed), and the p99 of
      admitted ``serve/e2e_ms`` vs the deadline.
    """
    import tempfile
    import threading

    import jax.numpy as jnp
    import numpy as np

    from hyperspace_tpu.manifolds import PoincareBall
    from hyperspace_tpu.resilience import faults
    from hyperspace_tpu.serve.batcher import RequestBatcher
    from hyperspace_tpu.serve.engine import QueryEngine
    from hyperspace_tpu.serve.errors import ServeError
    from hyperspace_tpu.telemetry import registry as telem

    detail: dict = {}
    reg = telem.default_registry()

    # --- chaos train: poisoned chunk -> one rollback -> finite finish
    from hyperspace_tpu.data.wordnet import synthetic_tree
    from hyperspace_tpu.models import poincare_embed as pe
    from hyperspace_tpu.train import loop as train_loop

    ds = synthetic_tree(depth=4, branching=3)
    cfg = pe.PoincareEmbedConfig(num_nodes=ds.num_nodes, dim=8,
                                 batch_size=64, neg_samples=8,
                                 burnin_steps=0)
    state, opt = pe.init_state(cfg, seed=0)
    step_fn = pe.make_train_step(cfg)
    pairs = jnp.asarray(ds.pairs)

    class _Run:  # duck-typed RunConfig (the loop's contract)
        steps, eval_every, log, tensorboard_dir = 24, 6, None, None
        ckpt_every, resume = 6, False
        rollback, rollback_lr_backoff = 2, 0.5

    base = reg.mark()
    with tempfile.TemporaryDirectory() as tmp:
        _Run.ckpt_dir = os.path.join(tmp, "ck")
        faults.install([faults.FaultSpec(site="train.step_nan",
                                         kind="nan", after=8)])
        try:
            state, loss = train_loop.run_loop(
                _Run(), state, lambda st: step_fn(cfg, opt, st, pairs))
        finally:
            faults.clear()
    delta = reg.snapshot(baseline=base)
    final_loss = float(loss)
    detail["chaos_train"] = {
        "steps": int(state.step),
        "final_loss": round(final_loss, 4),
        "final_loss_finite": final_loss == final_loss,
        "rollbacks": int(delta.get("resilience/rollbacks", 0)),
        "faults_fired": int(delta.get("fault/fired", 0)),
        "recovered": (final_loss == final_loss
                      and delta.get("resilience/rollbacks", 0) == 1),
    }

    # --- overload: bounded queue + ladder under 16 concurrent threads
    rng = np.random.default_rng(0)
    n, dim, k = 20_000, 16, 10
    deadline_ms, queue_max, workers, per_worker = 250.0, 4, 16, 6
    table = np.asarray(PoincareBall(1.0).expmap0(
        jnp.asarray(rng.standard_normal((n, dim)) * 0.3, jnp.float32)))
    eng = QueryEngine(table, ("poincare", 1.0))
    # down_after=3: the queue-full shed path must show BEFORE the
    # ladder degrades (instant cache-only refusals would otherwise
    # drain the queue so fast it never fills again)
    bat = RequestBatcher(eng, cache_size=0, queue_max=queue_max,
                         deadline_ms=deadline_ms, ladder_down_after=3,
                         ladder_up_after=3)
    # warm the compile OUTSIDE the deadline (first call pays XLA)
    bat.topk(rng.integers(0, n, size=64).tolist(), k, deadline_ms=60_000)
    base = reg.mark()
    outcomes = {"served": 0, "error": 0}
    kinds: dict = {}
    olock = threading.Lock()
    barrier = threading.Barrier(workers)
    max_level = {"v": 0}

    def worker(wid):
        wrng = np.random.default_rng(wid)
        barrier.wait()
        for _ in range(per_worker):
            ids = wrng.integers(0, n, size=64).tolist()
            try:
                bat.topk(ids, k)
                with olock:
                    outcomes["served"] += 1
            except ServeError as e:
                with olock:
                    outcomes["error"] += 1
                    kinds[e.kind] = kinds.get(e.kind, 0) + 1
            with olock:
                max_level["v"] = max(max_level["v"], bat._ladder.level)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # calm sequential traffic: the ladder must step back up (hysteresis)
    hot = rng.integers(0, 256, size=8).tolist()
    for _ in range(12):
        try:
            bat.topk(hot, k)
        except ServeError:
            pass  # early calm calls may still be cache-only
        if bat._ladder.level == 0:
            break
    delta = reg.snapshot(baseline=base)
    offered = workers * per_worker
    shed = int(delta.get("serve/shed", 0))
    e2e = delta.get("hist/serve/e2e_ms") or {}
    detail["overload"] = {
        "offered": offered, "queue_max": queue_max, "workers": workers,
        "deadline_ms": deadline_ms,
        "served": outcomes["served"], "errors": kinds,
        # shed = queue-full refusals (serve/shed); refused_rate adds the
        # ladder's cache-only refusals — both answer `overloaded`
        "shed": shed, "shed_rate": round(shed / offered, 3),
        "refused_rate": round(kinds.get("overloaded", 0) / offered, 3),
        "deadline_exceeded": int(delta.get("serve/deadline_exceeded", 0)),
        "degraded": int(delta.get("serve/degraded", 0)),
        "degrade_recovered": int(delta.get("serve/degrade_recovered", 0)),
        "degrade_max_level": max_level["v"],
        "ladder_recovered": bat._ladder.level == 0,
        "e2e_p99_ms": e2e.get("p99"),
        "p99_within_deadline": (e2e.get("p99") is not None
                                and e2e["p99"] <= deadline_ms),
    }
    ok = (detail["chaos_train"]["recovered"]
          and detail["overload"]["ladder_recovered"])
    return {"metric": "resilience_ok", "value": int(ok), "unit": "bool",
            "vs_baseline": None, "detail": detail}


def bench_multihost(repeats: int = 1, *, steps: int = 24,
                    chunk: int = 8) -> dict:
    """Pod-scaling leg (r19): the SAME chunked HGCN LP workload timed
    as a 1-process run and as a REAL 2-process × 2-virtual-device
    ``jax.distributed`` loopback fleet (``benchmarks/mh_worker.py
    --task bench`` — each process times its replica, process 0
    aggregates behind a coordination barrier).

    Rows per process count: step time, aggregate fleet throughput
    (``steps_per_s`` — nprocs replicas × steps / slowest process).
    Headline value = ``scaling_efficiency`` — 2-proc fleet throughput
    over 2× the 1-proc throughput (1.0 = perfect linear scaling; CPU
    loopback runs share cores, so well under 1.0 is expected and the
    TREND, not the level, is the signal).  ``multihost_ok`` gates the
    reading: per-chunk loss trajectories at both process counts must
    be finite and match (the degenerate-DP determinism contract —
    docs/multihost.md), so a scaling number from diverged replicas can
    never look green.

    Worker groups are bounded subprocesses, killed on ANY exit from
    this leg (including the SIGALRM ``_LegTimeout``) — a deadline here
    must not strand orphans holding the artifact's stdout tail.  The
    workers are pinned to the CPU, so the leg is labelled ``cpu`` and
    refuses to run in a process on another backend
    (:func:`_cpu_children_only`).
    """
    import subprocess
    import tempfile

    import numpy as np

    _cpu_children_only("bench_multihost")
    root = os.path.dirname(os.path.abspath(__file__))

    def _run_group(nprocs: int, workdir: str, timeout: float) -> dict:
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)  # workers set their own device count
        extra = env.get("PYTHONPATH")
        env["PYTHONPATH"] = os.pathsep.join(
            [root] + (extra.split(os.pathsep) if extra else []))
        procs = [subprocess.Popen(
            [sys.executable, "-m", "hyperspace_tpu.benchmarks.mh_worker",
             "--pid", str(p), "--nprocs", str(nprocs),
             "--port", str(port), "--workdir", workdir,
             "--task", "bench", "--steps", str(steps),
             "--chunk", str(chunk)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env) for p in range(nprocs)]
        outs = []
        try:
            for pr in procs:
                out, _ = pr.communicate(timeout=timeout)
                outs.append(out)
        finally:
            for pr in procs:  # no orphans on timeout or _LegTimeout
                if pr.poll() is None:
                    pr.kill()
                    pr.wait()
        for pr, out in zip(procs, outs):
            if pr.returncode != 0:
                raise RuntimeError(
                    f"multihost worker rc={pr.returncode}: {out[-400:]}")
        for out in outs:
            for line in out.splitlines():
                if line.startswith("RESULT "):
                    return json.loads(line[len("RESULT "):])
        raise RuntimeError("no RESULT line from multihost group")

    detail: dict = {"steps": steps, "chunk": chunk, "procs": {},
                    "backend": "cpu"}  # the workers', by construction
    with tempfile.TemporaryDirectory() as tmp:
        for nprocs in (1, 2):
            best = None
            for r in range(max(1, repeats)):
                wd = os.path.join(tmp, f"n{nprocs}_r{r}")
                os.makedirs(wd, exist_ok=True)
                res = _run_group(nprocs, wd, timeout=120)
                if best is None or res["steps_per_s"] > best["steps_per_s"]:
                    best = res
            detail["procs"][str(nprocs)] = {
                "step_time_s": round(best["step_time_s"], 6),
                "steps_per_s": round(best["steps_per_s"], 1),
                "elapsed_s": round(best["elapsed_s"], 3),
                "devices": best["devices"],
                "losses": [round(l, 6) for l in best["losses"]],
            }
    one, two = detail["procs"]["1"], detail["procs"]["2"]
    eff = two["steps_per_s"] / (2.0 * one["steps_per_s"])
    detail["scaling_efficiency"] = round(eff, 3)
    l1 = np.asarray(one["losses"])
    l2 = np.asarray(two["losses"])
    detail["multihost_ok"] = bool(
        np.all(np.isfinite(l1)) and np.all(np.isfinite(l2))
        and l1.shape == l2.shape and np.allclose(l1, l2, atol=1e-6))
    return {"metric": "multihost_scaling_efficiency",
            "value": detail["scaling_efficiency"],
            "unit": "x (2-proc fleet / 2x 1-proc throughput)",
            "vs_baseline": None, "detail": detail}


def bench_precision(repeats: int = 2) -> dict:
    """f32-vs-bf16 timing pairs on the SAME shapes (docs/precision.md).

    Two legs, each run under both precision presets so the pair in one
    artifact is an apples-to-apples MXU/bandwidth comparison:

    - **train step**: the HVAE sampled step (the policy's biggest train
      win — the conv/dense stacks are the model's whole MXU mass; the
      manifold latent stays f32 under both presets);
    - **serve scan**: one warm ``topk_neighbors`` batch over a synthetic
      Poincaré table — f32 scan vs bf16-scan + f32-rescore
      (``serve/engine.py`` precision modes).

    Value = train-step speedup (f32 ms / bf16 ms; > 1 means bf16 wins).
    On CPU backends bf16 often does NOT win — the pair is recorded
    either way so the trajectory is honest per backend.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hyperspace_tpu.manifolds import PoincareBall
    from hyperspace_tpu.models import hvae
    from hyperspace_tpu.serve.engine import QueryEngine

    rng = np.random.default_rng(0)
    n_steps = 10
    images = rng.random((1024, 28, 28)).astype(np.float32)
    train = {}
    for name in ("f32", "bf16"):
        cfg = hvae.HVAEConfig(precision=name, batch_size=256)
        model, opt, state = hvae.init_model(cfg, seed=0)
        x_all = jnp.asarray(images, cfg.dtype)
        t, _ = _time_steps(
            lambda st: hvae.train_step_sampled(model, opt, st, x_all)[:2],
            state, n_steps, max(2, repeats))
        train[name] = round(t / n_steps * 1e3, 3)

    n, dim, k, b = 20_000, 16, 10, 256
    table = np.asarray(PoincareBall(1.0).expmap0(
        jnp.asarray(rng.standard_normal((n, dim)) * 0.3, jnp.float32)))
    q = jnp.asarray(rng.integers(0, n, size=b), jnp.int32)
    serve = {}
    for name in ("f32", "bf16"):
        eng = QueryEngine(table, ("poincare", 1.0), precision=name)
        _, d = eng.topk_neighbors(q, k)  # compile + warm
        jax.device_get(d)
        times = []
        for _ in range(max(2, repeats)):
            t0 = time.perf_counter()
            _, d = eng.topk_neighbors(q, k)
            jax.device_get(d)
            times.append(time.perf_counter() - t0)
        serve[name] = round(min(times) * 1e3, 3)

    return {
        "metric": "precision_train_speedup",
        "value": round(train["f32"] / max(train["bf16"], 1e-9), 3),
        "unit": "x (f32 ms / bf16 ms)",
        "vs_baseline": None,
        "detail": {
            "train_workload": "hvae",
            "train_batch": 256,
            "train_step_ms": train,
            "serve_table": [n, dim],
            "serve_batch": b,
            "serve_k": k,
            "serve_scan_ms": serve,
            "serve_speedup": round(
                serve["f32"] / max(serve["bf16"], 1e-9), 3),
            "backend": jax.default_backend(),
        },
    }


def bench_big_table(repeats: int = 1, *, rows: int = 10_000_000,
                    dim: int = 8, ncells: int = 0,
                    train_rows: int = 200_000,
                    queries: int = 32, k: int = 10) -> dict:
    """Beyond-HBM table leg (r15, ROADMAP item 3): a ``rows``-node
    synthetic clustered Poincaré table **generated in host shards**
    (``parallel/host_table.HostEmbedTable.build`` — no [N, D] device
    residency during generation or index build), measured end to end:

    - **build_s**: the host-streamed IVF build (``serve/index.py``
      ``host_resident`` path — sampled k-means++ seeding, chunked
      Lloyd, spill on gathered rows only);
    - **lanes** f32 / bf16 / int8 / int4 / pq: measured per-lane
      scan-copy bytes (``table_mb`` — the capacity story: int8 is ~4×
      f32, int4 ~6×, pq ~10× at the default subspace count; pq counts
      its codebooks) and ``qps_at_recall99`` — warm probing queries/s
      at the smallest nprobe keeping recall@10 >= 0.99 vs the exact
      f32 scan (a lane whose quantization error never reaches 0.99
      reports 0.0 — the pq row is the honest one to watch);
    - **train**: host-resident planned-sparse step time
      (``train/host_embed.py`` — hot-row cache + chunk write-back) vs
      the in-HBM packed trainer at ``train_rows`` (a size both fit),
      plus the host path alone at the FULL table size;

    Headline value = the int8 lane's ``qps_at_recall99`` (the 4×-
    capacity lane has to hold production recall to count).  Per-lane
    and train failures degrade to ``*_error`` detail rows, never sink
    the leg.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from hyperspace_tpu.parallel.host_table import HostEmbedTable
    from hyperspace_tpu.serve.engine import QueryEngine
    from hyperspace_tpu.serve.index import auto_ncells, build_index

    rows, dim = int(rows), int(dim)
    spec = ("poincare", 1.0)
    rng = np.random.default_rng(0)
    ncl = min(512, max(rows // 64, 4))
    centers = rng.standard_normal((ncl, dim)) * 0.25

    def fill(start, nr):  # deterministic per block: ball points around
        r = np.random.default_rng((1234, start))  # clustered centers
        v = (centers[r.integers(0, ncl, nr)]
             + r.standard_normal((nr, dim)) * 0.05)
        nv = np.maximum(np.linalg.norm(v, axis=1, keepdims=True), 1e-12)
        return (np.tanh(nv) * v / nv).astype(np.float32)  # expmap0, c=1

    t0 = time.perf_counter()
    master = HostEmbedTable.build(rows, dim, fill,
                                  shard_rows=min(1 << 20, rows))
    gen_s = time.perf_counter() - t0
    # budget-shaped build knobs: ~√N cells capped at 512, ONE Lloyd
    # iteration (clustered synthetic data converges in one), wide
    # streamed blocks (fewer dispatches; device peak stays one block)
    ncells = int(ncells) or min(auto_ncells(rows), 512)
    t0 = time.perf_counter()
    idx = build_index(master, spec, ncells, iters=1, seed=0, balance=3.0,
                      chunk=min(1 << 18, max(rows, 4096)))
    build_s = time.perf_counter() - t0
    detail = {
        "rows": rows, "dim": dim, "ncells": ncells,
        "max_cell": idx.max_cell, "gen_s": round(gen_s, 2),
        "build_s": round(build_s, 2), "backend": jax.default_backend(),
        "table_mb": {}, "lanes": {},
    }

    # serve lanes: exact f32 ground truth once, then per-lane probing
    full = master.to_array()  # host copy for the engines (device work
    ids = rng.integers(0, rows, size=queries).astype(np.int32)  # is theirs)

    def timed_qps(e, nprobe=None):
        _, dd = e.topk_neighbors(ids, k, nprobe=nprobe)  # compile + warm
        jax.device_get(dd)
        ts = []
        for _ in range(max(2, repeats)):
            t0 = time.perf_counter()
            _, dd = e.topk_neighbors(ids, k, nprobe=nprobe)
            jax.device_get(dd)
            ts.append(time.perf_counter() - t0)
        return len(ids) / min(ts)

    exact = QueryEngine(full, spec)
    truth, _ = (np.asarray(a) for a in exact.topk_neighbors(ids, k))
    detail["exact_qps"] = round(timed_qps(exact), 1)
    del exact
    value = 0.0
    widths = [npb for npb in (1, 2, 4, 8, 16) if npb < ncells]
    for lane in ("f32", "bf16", "int8", "int4", "pq"):
        try:
            out = {"probes": {}, "qps_at_recall99": 0.0}
            # ONE engine per lane at the widest probe; each ladder step
            # narrows via the per-call nprobe override (the degradation
            # ladder's lever) — re-quantizing and re-uploading a 10M-row
            # table per width would be most of the lane's wall clock
            e = QueryEngine(full, spec, precision=lane, index=idx,
                            nprobe=max(widths))
            mb = e.scan_table.nbytes
            if e.scan_scale is not None:
                mb += e.scan_scale.nbytes
            if getattr(e, "pq_codebooks", None) is not None:
                mb += e.pq_codebooks.nbytes  # trained centers ride along
            out["table_mb"] = round(mb / 2**20, 1)
            detail["table_mb"][lane] = out["table_mb"]
            qps_at = 0.0
            for npb in widths:
                ii, _ = (np.asarray(a) for a in
                         e.topk_neighbors(ids, k, nprobe=npb))
                rec = float(np.mean([len(set(truth[j]) & set(ii[j])) / k
                                     for j in range(len(ids))]))
                qps = timed_qps(e, nprobe=npb)
                out["probes"][f"np{npb}"] = {"recall10": round(rec, 4),
                                             "qps": round(qps, 1)}
                if rec >= 0.99:
                    qps_at = qps
                    break  # smallest qualifying probe width is the
            del e
            out["qps_at_recall99"] = round(qps_at, 1)  # honest number
            detail["lanes"][lane] = out
            if lane == "int8":
                value = out["qps_at_recall99"]
        except Exception as err:  # noqa: BLE001 — per-lane failure
            # keeps the other lanes' rows (deadline _LegTimeout is a
            # BaseException and still flies through)
            detail["lanes"][f"{lane}_error"] = repr(err)
    del full

    # train: host-resident vs in-HBM at a size both fit, then host at
    # the full size (rsgd — packed rows are the table itself)
    try:
        from hyperspace_tpu.models import poincare_embed as pe
        from hyperspace_tpu.train import host_embed as he

        tn = int(min(train_rows, rows))
        cfg_t = pe.PoincareEmbedConfig(num_nodes=tn, dim=dim,
                                       batch_size=1024, neg_samples=10,
                                       optimizer="rsgd")
        pairs_t = rng.integers(0, tn, size=(100_000, 2)).astype(np.int32)
        cs, steps = 8, 24
        state, opt = pe.init_state(cfg_t, 0)
        tr = he.HostPlannedTrainer.from_state(cfg_t, opt, state,
                                              chunk_steps=cs, seed=1)
        tr.run(pairs_t, cs)  # warm
        t0 = time.perf_counter()
        tr.run(pairs_t, steps)
        host_ms = (time.perf_counter() - t0) / steps * 1e3
        state2, opt2 = pe.init_state(cfg_t, 0)
        # the packed program donates the state buffers — time the run
        # over the RETURNED state, never the consumed one
        state2, _ = he.run_planned_inhbm(cfg_t, opt2, state2, pairs_t,
                                         cs, chunk_steps=cs, seed=1)
        t0 = time.perf_counter()
        he.run_planned_inhbm(cfg_t, opt2, state2, pairs_t, steps,
                             chunk_steps=cs, seed=1)
        inhbm_ms = (time.perf_counter() - t0) / steps * 1e3
        detail["train"] = {
            "rows": tn, "chunk_steps": cs,
            "host_step_ms": round(host_ms, 2),
            "inhbm_step_ms": round(inhbm_ms, 2),
            "host_vs_inhbm": round(host_ms / max(inhbm_ms, 1e-9), 2),
        }
        if rows > tn:
            cfg_f = dataclasses.replace(cfg_t, num_nodes=rows)
            opt_f = pe.make_optimizer(cfg_f)
            trf = he.HostPlannedTrainer(
                cfg_f, opt_f, master, opt_f.init(jnp.zeros((1, dim))),
                jax.random.PRNGKey(0), chunk_steps=cs, seed=1)
            pairs_f = rng.integers(0, rows,
                                   size=(200_000, 2)).astype(np.int32)
            trf.run(pairs_f, cs)  # warm
            t0 = time.perf_counter()
            trf.run(pairs_f, steps)
            detail["train"]["host_step_ms_full"] = round(
                (time.perf_counter() - t0) / steps * 1e3, 2)
    except Exception as err:  # noqa: BLE001 — the serve lanes' rows
        # survive a train-leg failure (deadline flies through)
        detail["train_error"] = repr(err)

    return {"metric": "big_table_qps_at_recall99", "value": value,
            "unit": "queries/s", "vs_baseline": None, "detail": detail}


def bench_multitenant(repeats: int = 1, *, qps: float = 100.0,
                      duration_s: float = 2.0, table_rows: int = 4_000,
                      mix=(0.8, 0.15, 0.05)) -> dict:
    """Multi-tenant front door under a skewed tenant mix (docs/
    serving.md "Multi-tenant front door", ISSUE 20).

    One in-process HTTP front door over an :class:`EngineRegistry`
    (serve/registry.py) holding THREE tenant stacks (hot/mid/cold —
    the offered mix is Zipf-flavored: ``mix`` of the traffic each),
    driven open-loop through four phases:

    - **steady**: fixed offered load with the tenant sampled per
      request from ``mix`` — ``aggregate_qps`` (answered/s across all
      tenants, the headline) plus per-tenant p50/p95/p99 from each
      tenant's own ``serve/e2e_ms@tenant=`` histogram delta, and
      ``recompiles_steady`` (0 is the contract: the warmup walked
      every tenant's bucket ladder, and tenants share no programs
      beyond their scan signature);
    - **fairness**: the cold tenant's trickle is measured solo, then
      again while the hot tenant saturates the shared one-worker
      dispatch executor — the deficit-round-robin dispatcher bounds
      the damage, ``fairness`` = starved p99 / solo p99 (lower is
      better; the verdict allows max(200 ms, 20x solo) on a noisy
      CPU host);
    - **isolation**: every tenant's served top-k must be BITWISE the
      answer of a solo engine over its own table — cross-tenant cache
      or program leaks cannot fail politely;
    - **paging storm**: a second registry under a device budget that
      holds ONE resident engine; round-robin queries force whole-
      engine evict/re-admit cycles and every post-re-admission answer
      must stay bitwise (the host-resident artifact is the master
      copy), with the observed cold-admission latencies reported.

    Value = steady ``aggregate_qps`` (higher is better).
    ``multitenant_ok`` rolls up recompiles==0 + isolation + fairness +
    paging-actually-paged.
    """
    import asyncio
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from hyperspace_tpu.manifolds import PoincareBall
    from hyperspace_tpu.serve.engine import QueryEngine
    from hyperspace_tpu.serve.registry import EngineRegistry
    from hyperspace_tpu.serve.server import HttpFrontDoor
    from hyperspace_tpu.telemetry import registry as telem

    rng = np.random.default_rng(0)
    n, dim, k = table_rows, 16, 10
    names = ("hot", "mid", "cold")
    tables = {
        name: np.asarray(PoincareBall(1.0).expmap0(jnp.asarray(
            rng.standard_normal((n, dim)) * 0.3, jnp.float32)))
        for name in names
    }
    solo = {name: QueryEngine(tables[name], ("poincare", 1.0))
            for name in names}
    probe_ids = [0, 3, 17, 29]
    expect = {name: solo[name].topk_neighbors(
        np.asarray(probe_ids, np.int32), k) for name in names}
    reg = telem.default_registry()

    async def _post(host, port, payload):
        reader, writer = await asyncio.open_connection(host, port)
        try:
            body = json.dumps(payload).encode("utf-8")
            writer.write(
                (f"POST /v1/topk HTTP/1.1\r\nHost: bench\r\n"
                 f"Content-Length: {len(body)}\r\n"
                 "Connection: close\r\n\r\n").encode("latin-1") + body)
            await writer.drain()
            data = await reader.read()
        finally:
            writer.close()
        head, _, raw = data.partition(b"\r\n\r\n")
        status = int(head.split(None, 2)[1])
        try:
            return status, json.loads(raw.decode())
        except ValueError:
            return status, {}

    async def _drive(host, port, tenant_of, size, pass_qps, n_req,
                     seed):
        """Open-loop pass: ``tenant_of(i)`` names each request's
        tenant (clock-scheduled arrivals — a starved tenant queues,
        it never throttles the offered load)."""
        offsets = open_loop_arrivals(n_req, pass_qps, "poisson", seed)
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        tasks = []
        for i, off in enumerate(offsets):
            delay = t0 + float(off) - loop.time()
            if delay > 0:
                await asyncio.sleep(delay)
            payload = {"ids": rng.integers(0, n, size=size).tolist(),
                       "k": k, "tenant": tenant_of(i)}
            tasks.append(asyncio.ensure_future(
                _post(host, port, payload)))
        results = await asyncio.gather(*tasks, return_exceptions=True)
        elapsed = loop.time() - t0
        statuses: dict = {}
        for r in results:
            key = (f"error:{type(r).__name__}"
                   if isinstance(r, BaseException) else str(int(r[0])))
            statuses[key] = statuses.get(key, 0) + 1
        return statuses, elapsed

    def _tenant_p(delta, tenant):
        e2e = delta.get(f"hist/serve/e2e_ms@tenant={tenant}")
        if not e2e or not e2e.get("count"):
            return None
        return {"n": e2e["count"],
                **{q: e2e[q] for q in ("p50", "p95", "p99")}}

    def _mk_registry(budget_mb, art_dir):
        r = EngineRegistry(device_budget_mb=budget_mb,
                           max_wait_us=2000.0)
        for name in names:
            r.add_tenant(name, os.path.join(art_dir, name),
                         weight=1.0, window_s=0.0,
                         batcher_kw=dict(min_bucket=8, max_bucket=64,
                                         cache_size=0, queue_max=256))
        return r

    async def _probe_bitwise(host, port, name):
        """One tenant's served top-k vs its solo engine, bit for bit
        — the structural-isolation (and post-re-admission) check."""
        status, body = await _post(
            host, port, {"ids": probe_ids, "k": k, "tenant": name})
        if status != 200:
            return False
        li, ld = (np.asarray(a) for a in expect[name])
        return (np.array_equal(li, np.asarray(body["neighbors"]))
                and np.array_equal(
                    ld.astype(np.float32).view(np.uint32),
                    np.asarray(body["dists"],
                               np.float32).view(np.uint32)))

    async def _run(art_dir):
        detail: dict = {
            "num_nodes": n, "dim": dim, "k": k, "tenants": list(names),
            "mix": list(mix), "offered_qps": qps,
            "duration_s": duration_s,
            "backend": jax.default_backend(),
        }
        registry = _mk_registry(0.0, art_dir)
        door = HttpFrontDoor(registry=registry, max_wait_us=2000)
        await door.start()
        c0 = reg.get("jax/recompiles")
        # closed-loop warmup: every tenant × every bucket rung, so the
        # mixed-tenant timed phase can never hand the compiler a fresh
        # shape (collation may pad any tenant's queue to any rung)
        for name in names:
            for b in registry.resolve(name).batcher.buckets:
                await _post(door.host, door.port,
                            {"ids": rng.integers(0, n, size=b).tolist(),
                             "k": k, "tenant": name})
        c1 = reg.get("jax/recompiles")
        detail["recompiles_warmup"] = c1 - c0

        # --- steady: Zipf-mix offered load, per-tenant percentiles ---
        n_req = max(16, int(qps * duration_s))
        picks = rng.choice(len(names), size=n_req, p=list(mix))
        base = reg.mark()
        statuses, elapsed = await _drive(
            door.host, door.port, lambda i: names[picks[i]], 16, qps,
            n_req, 7)
        delta = reg.snapshot(baseline=base)
        answered = sum(v for s, v in statuses.items()
                       if not s.startswith("error"))
        detail["steady"] = {
            "statuses": statuses,
            "aggregate_qps": round(answered / max(elapsed, 1e-9), 1),
            "per_tenant_ms": {t: _tenant_p(delta, t) for t in names},
        }
        agg = delta.get("hist/serve/e2e_ms")
        if not agg or not agg.get("count"):
            await door.drain()
            raise RuntimeError(
                f"multitenant: no successful steady request — "
                f"{statuses}")
        detail["aggregate_qps"] = detail["steady"]["aggregate_qps"]
        detail["steady"]["p99_ms"] = agg["p99"]
        detail["recompiles_steady"] = reg.get("jax/recompiles") - c1

        # --- isolation: every tenant bitwise vs its solo engine ------
        # (probed BEFORE the fairness flood: the flood legitimately
        # walks the hot tenant down its degradation ladder, and a
        # degraded answer is supposed to differ)
        isolation = {t: await _probe_bitwise(door.host, door.port, t)
                     for t in names}
        detail["isolation_bitwise"] = isolation

        # --- fairness: cold trickle solo, then under a hot flood ----
        trickle_qps, trickle_n = 25.0, 30
        base = reg.mark()
        await _drive(door.host, door.port, lambda i: "cold", 16,
                     trickle_qps, trickle_n, 21)
        solo_p = _tenant_p(reg.snapshot(baseline=base), "cold")
        base = reg.mark()
        flood_n = max(32, int(qps * 6 * 1.2))
        _, _ = await asyncio.gather(
            _drive(door.host, door.port, lambda i: "hot", 16, qps * 6,
                   flood_n, 33),
            _drive(door.host, door.port, lambda i: "cold", 16,
                   trickle_qps, trickle_n, 34))
        starved_p = _tenant_p(reg.snapshot(baseline=base), "cold")
        if solo_p and starved_p:
            solo_p99 = max(solo_p["p99"], 0.05)
            detail["fairness_detail"] = {
                "solo_p99_ms": solo_p["p99"],
                "starved_p99_ms": starved_p["p99"],
                "trickle_qps": trickle_qps, "flood_qps": qps * 6,
            }
            detail["starved_p99_ms"] = starved_p["p99"]
            detail["fairness"] = round(starved_p["p99"] / solo_p99, 3)
            fairness_ok = starved_p["p99"] <= max(200.0, 20 * solo_p99)
        else:
            detail["fairness_detail"] = {"error": "empty fairness pass"}
            fairness_ok = False
        detail["fairness_ok"] = fairness_ok
        await door.drain()

        # --- paging storm: budget holds ONE engine; round-robin ------
        table_mb = tables["hot"].nbytes / (1 << 20)
        budget_mb = round(table_mb * 1.5, 3)  # one fits, two never do
        storm = _mk_registry(budget_mb, art_dir)
        sdoor = HttpFrontDoor(registry=storm, max_wait_us=2000)
        await sdoor.start()
        cold_ms, paged_bitwise = [], True
        for _round in range(2):
            for name in names:
                t0 = time.perf_counter()
                ok = await _probe_bitwise(sdoor.host, sdoor.port, name)
                cold_ms.append(round(
                    (time.perf_counter() - t0) * 1e3, 1))
                paged_bitwise = paged_bitwise and ok
        sstats = storm.stats()
        admits = sum(s["registry"]["admissions"]
                     for s in sstats.values())
        evicts = sum(s["registry"]["evictions"]
                     for s in sstats.values())
        await sdoor.drain()
        paging_ok = paged_bitwise and evicts > 0 and admits > len(names)
        detail["paging"] = {
            "device_budget_mb": budget_mb,
            "table_mb": round(table_mb, 3),
            "admissions": admits, "evictions": evicts,
            "bitwise_after_readmit": paged_bitwise,
            "cold_admit_ms": cold_ms,
        }

        detail["multitenant_ok"] = bool(
            detail["recompiles_steady"] == 0
            and all(isolation.values()) and fairness_ok and paging_ok)
        return detail

    with tempfile.TemporaryDirectory() as tmp:
        from hyperspace_tpu.serve import export_artifact

        for name in names:
            export_artifact(os.path.join(tmp, name), tables[name],
                            ("poincare", 1.0), model_config={"c": 1.0})
        detail = asyncio.run(_run(tmp))
    return {"metric": "multitenant_agg_qps",
            "value": detail["aggregate_qps"], "unit": "queries/s",
            "vs_baseline": None, "detail": detail}


def _get(d, *path):
    """Nested dict lookup returning None on any missing key."""
    for k in path:
        if not isinstance(d, dict) or k not in d:
            return None
        d = d[k]
    return d


# compact-headline fields, highest priority first: when the compact line
# must shrink to fit the tail budget, keys are dropped from the END of
# this list.  Each entry: (compact_key, path into the full result).
_COMPACT_FIELDS = (
    ("step_time_s", ("detail", "step_time_s")),
    ("frac_hbm_roofline", ("detail", "frac_hbm_roofline")),
    ("bytes_per_step", ("detail", "bytes_per_step")),
    ("repeat_spread", ("detail", "repeat_spread")),
    ("error", ("detail", "error")),
    ("failed_benchmark", ("detail", "failed_benchmark")),
    ("budget_exhausted", ("detail", "budget_exhausted")),
    ("skipped_legs", ("detail", "skipped_legs")),
    ("timed_out_legs", ("detail", "timed_out_legs")),
    ("serve_qps", ("detail", "serve", "qps")),
    ("serve_recompiles_steady", ("detail", "serve", "recompiles_steady")),
    # per-qps-bucket p50/p95/p99 (ms) from the serve/e2e_ms histogram:
    # the first path is the auto-mode nested leg, the second fires when
    # bench_serve IS the headline (--metric serve) and detail is flat
    ("serve_latency_ms", ("detail", "serve", "latency_ms")),
    ("latency_ms", ("detail", "latency_ms")),
    # qps at recall@10 >= 0.99 over the IVF index (r10): first path is
    # auto mode's nested serve leg, second fires when bench_serve IS
    # the headline (--metric serve)
    ("serve_qps_r99", ("detail", "serve", "ivf", "qps_at_recall99")),
    ("qps_r99", ("detail", "ivf", "qps_at_recall99")),
    # fused/two_stage qps ratio at the largest bucket (r12): first path
    # is auto mode's nested serve leg, second fires when bench_serve IS
    # the headline (--metric serve)
    ("serve_fused_speedup",
     ("detail", "serve", "fused_vs_unfused", "serve_fused_speedup")),
    ("fused_speedup",
     ("detail", "fused_vs_unfused", "serve_fused_speedup")),
    # HTTP front door at fixed offered load (r13): aggregate p99 and
    # the overload pass's 429 shed rate — one path pair per field for
    # auto mode's nested leg vs --metric serve_http's flat detail.
    # Lower is better for both; scripts/bench_trend.py registers the
    # shed/deadline tokens direction-correctly.
    ("http_p99_ms", ("detail", "serve_http", "http_p99_ms")),
    ("http_p99_ms", ("detail", "http_p99_ms")),
    ("http_shed_rate", ("detail", "serve_http", "shed_rate")),
    ("http_shed_rate", ("detail", "shed_rate")),
    # live mutable index leg (r18): steady p99 under a concurrent
    # upsert stream, p99 across the blue-green flip, upsert-to-visible
    # latency and the three zero-contract columns (errors, stale
    # results, post-prewarm recompiles roll up into live_ok).  First
    # path is auto mode's nested leg, second fires when
    # bench_live_index IS the headline (--metric live_index).
    ("live_p99_ms", ("detail", "live_index", "live_p99_ms")),
    ("live_p99_ms", ("detail", "live_p99_ms")),
    ("p99_during_rollover_ms",
     ("detail", "live_index", "p99_during_rollover_ms")),
    ("p99_during_rollover_ms", ("detail", "p99_during_rollover_ms")),
    ("upsert_visible_ms",
     ("detail", "live_index", "freshness", "upsert_visible_ms", "p99")),
    ("upsert_visible_ms",
     ("detail", "freshness", "upsert_visible_ms", "p99")),
    ("live_ok", ("detail", "live_index", "live_ok")),
    ("live_ok", ("detail", "live_ok")),
    ("live_recall_vs_oracle",
     ("detail", "live_index", "recall_vs_oracle")),
    ("live_recall_vs_oracle", ("detail", "recall_vs_oracle")),
    # cold-start time-to-first-query at warm cache + prewarm (r14) and
    # its recompile contract: first path pair for auto mode's nested
    # leg, second when bench_cold_start IS the headline
    ("cold_ttfq_ms", ("detail", "cold_start", "cold_ttfq_ms")),
    ("cold_ttfq_ms", ("detail", "cold_ttfq_ms")),
    ("cold_recompiles_steady",
     ("detail", "cold_start", "recompiles_steady")),
    ("cold_recompiles_steady", ("detail", "cold_recompiles_steady")),
    # beyond-HBM big-table leg (r15): the int8 lane's qps at recall
    # >= 0.99, its scan-copy megabytes (4× capacity vs f32 — lower is
    # better, bench_trend's bytes/mb tokens), the streamed IVF build
    # time and the host-resident vs in-HBM train-step ratio.  First
    # path is auto mode's nested leg, second fires when
    # bench_big_table IS the headline (--metric big_table)
    ("big_qps_r99_int8",
     ("detail", "big_table", "lanes", "int8", "qps_at_recall99")),
    ("big_qps_r99_int8", ("detail", "lanes", "int8", "qps_at_recall99")),
    ("big_table_mb_int8", ("detail", "big_table", "table_mb", "int8")),
    ("big_table_mb_int8", ("detail", "table_mb", "int8")),
    # r16 sub-int8 lanes: the capacity ladder below int8 (int4 packed
    # nibbles + f16 scales; pq codes + codebooks) — same lower-is-
    # better mb gating via bench_trend's size tokens
    ("big_table_mb_int4", ("detail", "big_table", "table_mb", "int4")),
    ("big_table_mb_int4", ("detail", "table_mb", "int4")),
    ("big_table_mb_pq", ("detail", "big_table", "table_mb", "pq")),
    ("big_table_mb_pq", ("detail", "table_mb", "pq")),
    ("big_build_s", ("detail", "big_table", "build_s")),
    ("big_build_s", ("detail", "build_s")),
    ("big_host_step_ms",
     ("detail", "big_table", "train", "host_step_ms")),
    ("big_host_step_ms", ("detail", "train", "host_step_ms")),
    ("precision_train_ms", ("detail", "precision", "train_step_ms")),
    ("precision_serve_ms", ("detail", "precision", "serve_scan_ms")),
    # pod-scale loopback scaling leg (r19): 2-proc fleet throughput
    # over 2× 1-proc (higher is better — bench_trend's scaling/
    # efficiency tokens), gated by the cross-process-count loss-match
    # verdict (multihost_ok — a sentinel, excluded from trend gating).
    # First path is auto mode's nested leg, second fires when
    # bench_multihost IS the headline (--metric multihost)
    ("multihost_scaling_efficiency",
     ("detail", "multihost", "scaling_efficiency")),
    ("multihost_scaling_efficiency", ("detail", "scaling_efficiency")),
    ("multihost_ok", ("detail", "multihost", "multihost_ok")),
    ("multihost_ok", ("detail", "multihost_ok")),
    # multi-tenant front door leg (r20): steady aggregate qps at the
    # Zipf mix (higher is better — bench_trend's qps token), the DRR
    # fairness ratio + the starved tenant's contended p99 (lower is
    # better — the fairness/starved tokens), gated by the rolled-up
    # verdict (multitenant_ok — a sentinel, excluded from trend
    # gating).  First path is auto mode's nested leg, second fires
    # when bench_multitenant IS the headline (--metric multitenant)
    ("multitenant_agg_qps", ("detail", "multitenant", "aggregate_qps")),
    ("multitenant_agg_qps", ("detail", "aggregate_qps")),
    ("tenant_fairness", ("detail", "multitenant", "fairness")),
    ("tenant_fairness", ("detail", "fairness")),
    ("starved_p99_ms", ("detail", "multitenant", "starved_p99_ms")),
    ("starved_p99_ms", ("detail", "starved_p99_ms")),
    ("multitenant_ok", ("detail", "multitenant", "multitenant_ok")),
    ("multitenant_ok", ("detail", "multitenant_ok")),
    # failure-domain leg (PR 9): chaos recovery + the shed-rate column
    ("resilience_ok", ("detail", "resilience", "ok")),
    ("shed_rate", ("detail", "resilience", "overload", "shed_rate")),
    ("chaos_rollbacks",
     ("detail", "resilience", "chaos_train", "rollbacks")),
    ("frac_clustered", ("detail", "frac_clustered")),
    ("num_nodes", ("detail", "num_nodes")),
    ("devices", ("detail", "devices")),
    ("backend", ("detail", "backend")),
    ("use_att", ("detail", "use_att")),
    ("lr", ("detail", "lr")),
    ("loss", ("detail", "loss")),
    ("att_step_s", ("detail", "use_att_arm", "step_time_s")),
    ("att_samples_per_s_per_chip",
     ("detail", "use_att_arm", "samples_per_s_per_chip")),
    ("poincare_epoch_s", ("detail", "poincare_embed_epoch_time_s")),
    ("sampled_samples_per_s",
     ("detail", "hgcn_sampled", "supervised_samples_per_s")),
    ("sampled_incl_samples_per_s",
     ("detail", "hgcn_sampled", "sampling_inclusive_samples_per_s")),
    ("realistic_mean_step_s", ("detail", "realistic", "mean_step_s")),
    ("realistic_att_step_s", ("detail", "realistic", "att_step_s")),
    ("realistic_frac_clustered",
     ("detail", "realistic", "mean_frac_clustered")),
    ("hvae_scan_chunk_step_ms",
     ("detail", "workloads", "hvae", "scan_chunk_step_ms")),
    ("product_scan_chunk_step_ms",
     ("detail", "workloads", "product_embed", "scan_chunk_step_ms")),
    ("reorder", ("detail", "reorder")),
    ("source", ("detail", "source")),
    ("dtype", ("detail", "dtype")),
)

# hard byte budget for the LAST stdout line.  The driver records only the
# final 2000 characters of stdout (BENCH_r04.json was truncated to
# ``parsed: null`` when the single ever-growing JSON line outgrew that);
# 1400 leaves headroom for the newline and any driver framing.
COMPACT_LIMIT = 1400


def _json_default(o):
    """Last-resort serializer: a leg dropping a numpy scalar/array (or
    anything else json can't take) into detail must degrade that VALUE,
    never swallow the whole emit — BENCH_r04 ended with ``parsed: null``
    and rc=0, i.e. a run that completed but whose artifact didn't."""
    try:
        import numpy as np

        if isinstance(o, np.generic):
            return o.item()
        if isinstance(o, np.ndarray):
            return o.tolist()
    except Exception:  # noqa: BLE001  # hyperlint: disable=swallow-base-exception — numpy import failure: degrade to str(o) below
        pass
    return str(o)


def compact_headline(result: dict, limit: int = COMPACT_LIMIT) -> str:
    """One SMALL self-sufficient JSON line — always printed LAST.

    Carries metric/value/unit/vs_baseline plus a priority-ordered subset
    of the detail; guaranteed ≤ ``limit`` characters by dropping
    lowest-priority detail keys (never the metric/value themselves).
    """
    fields = []
    for key, path in _COMPACT_FIELDS:
        v = _get(result, *path)
        if v is not None:
            if isinstance(v, str) and len(v) > 200:
                v = v[:200]
            fields.append((key, v))
    while True:
        line = json.dumps({
            "metric": result.get("metric"),
            "value": result.get("value"),
            "unit": result.get("unit"),
            "vs_baseline": result.get("vs_baseline"),
            "detail": dict(fields),
        }, default=_json_default)
        if len(line) <= limit or not fields:
            return line
        fields.pop()


def emit(result: dict) -> None:
    """Print the full result, then the compact headline as the FINAL line.

    The driver's tail capture (last 2000 chars of stdout) therefore always
    contains one complete parseable JSON record with the headline metric,
    regardless of how large the full detail grows.  The full record is
    also written to ``bench_full.json`` beside this file.

    The compact line is the contract: nothing that can go wrong with the
    full record (unserializable detail, a read-only checkout) may keep
    it off stdout — a final fallback headline prints even if the compact
    builder itself raises.
    """
    import os

    try:
        full_line = json.dumps(result, default=_json_default)
    except Exception:  # noqa: BLE001 — circular detail etc.
        full_line = None
    if full_line is not None:
        try:
            # BENCH_FULL_JSON redirects the artifact copy (tests point it
            # at a tmp dir so a real subprocess run never clobbers the
            # checkout's last genuine bench_full.json)
            path = os.environ.get("BENCH_FULL_JSON") or os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "bench_full.json")
            with open(path, "w") as f:
                f.write(full_line + "\n")
        except OSError:
            pass  # read-only checkout: stdout still carries everything
        print(full_line)
    try:
        line = compact_headline(result)
    except Exception:  # noqa: BLE001 — the headline must still land
        line = json.dumps({"metric": result.get("metric", "error"),
                           "value": result.get("value", 0), "unit": "",
                           "vs_baseline": None,
                           "detail": {"emit_degraded": True}},
                          default=_json_default)
    print(line)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--metric",
                   choices=["auto", "hgcn", "poincare", "serve",
                            "serve_http", "live_index", "cold_start",
                            "big_table", "multihost", "multitenant"],
                   default="auto")
    p.add_argument("--big-rows", type=int, default=10_000_000,
                   help="--metric big_table: synthetic table rows "
                        "(generated in host shards; r15 beyond-HBM leg)")
    p.add_argument("--big-dim", type=int, default=8,
                   help="--metric big_table: table feature width")
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--dtype", choices=["float32", "bfloat16"], default="float32")
    p.add_argument("--agg-dtype", choices=["float32", "bfloat16"],
                   default="bfloat16")
    p.add_argument("--use-att", action="store_true",
                   help="attention aggregation (GAT-style) instead of mean")
    p.add_argument("--decoder-dtype", choices=["float32", "bfloat16"],
                   default="bfloat16")
    p.add_argument("--budget-s", type=float,
                   default=float(os.environ.get("BENCH_BUDGET_S",
                                                DEFAULT_BUDGET_S)),
                   help="wall-clock budget: optional legs are skipped "
                        "once they can't fit, and a watchdog emits the "
                        "partial artifact at the deadline")
    p.add_argument("--compile-cache-dir", default=None,
                   help="persistent XLA compilation cache directory "
                        "(hyperspace_tpu/compile_cache.py; default ON "
                        "where JAX_COMPILATION_CACHE_DIR says, else "
                        "under <repo>/.cache/jax_compile; 0 disables) — "
                        "round N+1's compiles become deserializations")
    args = p.parse_args()

    # cache BEFORE any leg compiles; an unusable cache dir is a usage
    # error, as in the CLIs — a run that silently compiles cold reads
    # as a slow chip
    from hyperspace_tpu import compile_cache as _compile_cache

    try:
        cc_dir = _compile_cache.activate(args.compile_cache_dir)
    except ValueError as e:
        raise SystemExit(str(e)) from None

    import functools
    import traceback

    guard = _BudgetGuard(args.budget_s)
    holder: dict = {"result": None}
    # sub-10 s budgets (tests, smoke) keep the leg-skip behavior but not
    # the watchdog — a near-zero timer would race the normal emit path
    if args.budget_s >= 10:
        guard.arm(holder)

    hgcn_fn = functools.partial(bench_hgcn, dtype=args.dtype,
                                agg_dtype=args.agg_dtype,
                                use_att=args.use_att,
                                decoder_dtype=args.decoder_dtype)
    primary = {"poincare": bench_poincare,
               "serve": bench_serve,
               "serve_http": bench_serve_http,
               "live_index": bench_live_index,
               "cold_start": bench_cold_start,
               "big_table": functools.partial(
                   bench_big_table, rows=args.big_rows,
                   dim=args.big_dim),
               "multihost": bench_multihost,
               "multitenant": bench_multitenant}.get(args.metric,
                                                     hgcn_fn)
    primary_name = args.metric if args.metric != "auto" else "hgcn"

    # the headline metric NEVER switches silently: a failure of the
    # selected benchmark (hgcn under auto) is reported as metric="error"
    # with the traceback, not papered over with a different green metric
    failed = False
    try:
        try:
            # a positive budget bounds even the headline benchmark: a
            # budget_exhausted record that parses beats a perfect record
            # the driver's hard timeout never saw.  budget<=0 keeps the
            # documented "skip every optional leg, run the headline
            # unbounded" escape hatch.
            with (_deadline(guard.remaining()) if args.budget_s > 0
                  else contextlib.nullcontext()):
                result = primary(repeats=args.repeats)
        except _LegTimeout:
            result = {"metric": "budget_exhausted", "value": 0, "unit": "",
                      "vs_baseline": None,
                      "detail": {"budget_exhausted": True,
                                 "timed_out_legs": [primary_name]}}
        except Exception as e:
            failed = True
            result = {"metric": "error", "value": 0, "unit": "",
                      "vs_baseline": None,
                      "detail": {"error": repr(e),
                                 "traceback": traceback.format_exc(),
                                 "failed_benchmark": primary_name}}
        holder["result"] = result  # legs below mutate detail in place,
        skipped: list = []         # so the watchdog emits live progress
        timed_out: list = []

        def leg(name: str, min_s: float, fn) -> None:
            """Run one optional detail leg if the remaining budget can
            plausibly fit it (``min_s`` — a rough floor, not a promise),
            under a hard deadline at the remaining budget (BENCH_r05:
            the floor check alone lets one slow leg on an experimental
            backend eat the whole budget); skipped and timed-out legs
            are listed in the artifact instead of silently missing."""
            if guard.remaining() < min_s:
                skipped.append(name)
                return
            try:
                with _deadline(guard.remaining()):
                    fn(result["detail"])
            except _LegTimeout:
                timed_out.append(name)
            except Exception as e:  # noqa: BLE001 — legs never sink the run
                result["detail"][f"{name}_error"] = repr(e)

        if args.metric == "auto":
            # both BASELINE metrics in the one JSON line: hgcn stays the
            # headline (or the error record), the poincare epoch time
            # rides in detail either way
            def poincare_leg(d):
                pr = bench_poincare(repeats=max(1, args.repeats - 1))
                d["poincare_embed_epoch_time_s"] = pr["value"]
                d["poincare"] = pr["detail"]

            def sampled_leg(d):  # minibatch trainer (honest unit)
                d["hgcn_sampled"] = bench_sampled(
                    repeats=max(1, args.repeats - 1))

            def realistic_leg(d):  # disk → loader → reorder → cluster
                from hyperspace_tpu.benchmarks.hgcn_bench import (
                    run_realistic_bench,
                )

                d["realistic"] = run_realistic_bench(
                    repeats=max(1, args.repeats - 1))

            def workloads_leg(d):
                # workloads 3-5 one-liners + the 4k-token flash fwd+bwd
                # leg; these ms-scale legs keep their own repeats default
                # (4): min-of-more-repeats is the r04 drift fix
                from hyperspace_tpu.benchmarks.workloads_bench import (
                    run_workloads_bench,
                )

                d["workloads"] = run_workloads_bench()

            def serve_leg(d):  # serving perf, tracked from PR 4 on
                r = bench_serve(repeats=max(1, args.repeats - 1))
                d["serve"] = {"qps": r["value"], **r["detail"]}

            def serve_http_leg(d):  # open-loop HTTP latency (r13)
                r = bench_serve_http(repeats=max(1, args.repeats - 1))
                d["serve_http"] = {"p99_ms": r["value"], **r["detail"]}

            def live_index_leg(d):  # live upserts + rollover (r18)
                r = bench_live_index()
                d["live_index"] = r["detail"]

            def cold_start_leg(d):  # restart TTFQ + cache regimes (r14)
                r = bench_cold_start()
                d["cold_start"] = r["detail"]

            def precision_leg(d):  # f32/bf16 pairs, tracked from PR 5 on
                r = bench_precision(repeats=max(1, args.repeats - 1))
                d["precision"] = {"train_speedup": r["value"],
                                  **r["detail"]}

            def big_table_leg(d):  # beyond-HBM table lanes (r15) — a
                # scaled-down table in auto mode (the full 10M-row leg
                # is --metric big_table); still host-resident end to
                # end, so the streamed build + hot-row trainer + all
                # three lanes exercise the real code paths every round
                r = bench_big_table(repeats=max(1, args.repeats - 1),
                                    rows=300_000, ncells=192,
                                    train_rows=100_000)
                d["big_table"] = r["detail"]
                d["big_table"]["big_table_qps_at_recall99"] = r["value"]

            def resilience_leg(d):  # chaos recovery + shed rate (PR 9)
                r = bench_resilience()
                d["resilience"] = {"ok": r["value"], **r["detail"]}

            def multihost_leg(d):  # pod-scale loopback scaling (r19)
                r = bench_multihost()
                d["multihost"] = r["detail"]

            def multitenant_leg(d):  # engine registry + DRR (r20)
                r = bench_multitenant()
                d["multitenant"] = r["detail"]

            def use_att_leg(d):
                # the attention arm on the same graph/protocol (VERDICT
                # r3 #1).  Distinct key: detail["use_att"] is the
                # headline's config-as-executed bool and must not be
                # clobbered.  With --use-att the primary already IS this
                # arm — don't run the multi-minute bench twice.
                src = (d if args.use_att
                       else hgcn_fn(repeats=max(1, args.repeats - 1),
                                    use_att=True)["detail"])
                d["use_att_arm"] = {
                    "step_time_s": src["step_time_s"],
                    "samples_per_s_per_chip": round(
                        src["num_nodes"] / src["step_time_s"]
                        / src["devices"], 1),
                    "lr": src["lr"],
                    "clip_norm": src["clip_norm"],
                    "loss": src["loss"],
                }

            # rough per-leg floors (seconds on the usual remote chip) —
            # generous enough that a leg given the green light normally
            # finishes well before the watchdog deadline
            leg("poincare", 60, poincare_leg)
            leg("hgcn_sampled", 45, sampled_leg)
            leg("serve_qps", 40, serve_leg)
            leg("serve_http", 35, serve_http_leg)
            leg("live_index", 40, live_index_leg)
            leg("cold_start", 60, cold_start_leg)
            leg("big_table", 75, big_table_leg)
            leg("precision", 40, precision_leg)
            leg("resilience", 25, resilience_leg)
            leg("multihost", 90, multihost_leg)
            leg("multitenant", 45, multitenant_leg)
            leg("realistic", 150, realistic_leg)
            leg("workloads", 90, workloads_leg)
            leg("use_att_arm", 0 if args.use_att else 120, use_att_leg)

        try:
            # the run's telemetry counters (prep-cache, prefetch,
            # recompiles — docs/observability.md) ride in the artifact:
            # cross-round counter drift is a regression signal the
            # timing numbers alone can't show
            from hyperspace_tpu.telemetry import registry as _telem

            snap = _telem.snapshot()
            if snap:
                result["detail"]["telemetry"] = snap
        except Exception:  # noqa: BLE001  # hyperlint: disable=swallow-base-exception — optional diagnostics never sink the bench; the artifact must still emit
            pass
        result["detail"]["budget_s"] = args.budget_s
        result["detail"]["compile_cache"] = cc_dir or "off"
        result["detail"]["elapsed_s"] = round(guard.elapsed(), 1)
        if skipped:
            result["detail"]["skipped_legs"] = skipped
        if timed_out:
            result["detail"].setdefault("timed_out_legs", []).extend(timed_out)
        if guard.claim_emit():
            emit(result)
    finally:
        guard.disarm()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
