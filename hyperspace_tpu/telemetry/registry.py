"""Process-wide counter/gauge registry — the ONE home of run counters.

PR 1 left the hot path's bookkeeping scattered: prep-cache hits were a
``print`` per event, prefetch stalls were invisible, checkpoint cost was
nowhere, and recompiles only showed up as mysterious wall-clock cliffs.
This registry replaces the ad-hoc lines with named counters/gauges that
(1) any module can bump with one cheap dict-op (no device work, no
host sync — safe on the per-dispatch path), (2) the training loop
snapshots into every JSONL log record (``ctr/*`` fields) and into one
final ``telemetry_summary`` record, and (3) the bench can read directly.

The COUNTER CATALOG lives in docs/observability.md; every name
incremented anywhere in the package must be documented there —
``scripts/check_telemetry_catalog.py`` (run inside the test suite)
fails the build otherwise.  Add the doc row when you add the counter.

Counters are monotonic sums (floats allowed: seconds accumulate);
gauges are last-write-wins levels (queue depth, bytes on disk);
histograms (:mod:`hyperspace_tpu.telemetry.histogram` — the third
kind, ``observe(name, value)``) are streaming latency distributions
surfaced as ``hist/<name>`` snapshot entries with count/sum/min/max
and p50/p90/p95/p99.  All ops are lock-guarded — the prefetch worker
thread increments concurrently with the training loop.

``install_jax_monitoring_hook`` subscribes to :mod:`jax.monitoring`'s
duration events and turns backend compiles into ``jax/recompiles`` /
``jax/compile_s`` — the counter that catches a shape-unstable stepper
recompiling every chunk (the failure the chunked loop's donation +
static scan length is supposed to rule out).  With the persistent
compilation cache active (:mod:`hyperspace_tpu.compile_cache`) the same
hook also counts ``jax/compile_cache_hit`` (executables deserialized
from disk — the backend compile never ran) and
``jax/compile_cache_miss`` (backend compiles while the cache was
enabled; each writes a new entry), so cache hit rates ride into every
JSONL record and bench artifact for free.  jax's trace and lowering
times are summed beside them (``jax/trace_s``, ``jax/lower_s``), and
each of the three events is also a span (``jit_trace``, ``jit_lower``,
``compile``) by function name: in the start-up timeline while that is
open, in the tracer while that is on (telemetry/trace.py).
"""

from __future__ import annotations

import threading
import time
from typing import Optional

_BACKEND_COMPILE_SUBSTR = "backend_compile"
# jax's two stages before the backend compile: event -> (span, counter)
_JIT_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": ("jit_trace", "jax/trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("jit_lower", "jax/lower_s")}
_CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class Registry:
    """Named monotonic counters + last-write gauges, thread-safe."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, float] = {}
        # gauge -> (value, write seq): the seq lets a per-run snapshot
        # exclude stale gauges a PRIOR in-process run set (see mark())
        self._gauges: dict[str, tuple] = {}
        self._hists: dict = {}  # name -> histogram.Histogram
        self._seq = 0

    def inc(self, name: str, value: float = 1) -> None:
        """Add ``value`` to counter ``name`` (creating it at 0)."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._seq += 1
            self._gauges[name] = (value, self._seq)

    def observe(self, name: str, value: float) -> None:
        """Record ``value`` into streaming histogram ``name`` (created
        on first observe).  The registry lock only guards the name
        lookup; the histogram's own lock guards the counts — an
        ``observe`` never blocks behind a ``snapshot`` of OTHER names.
        The price: an observe racing :meth:`reset` may land in the
        cleared epoch and be dropped with it (unlike ``inc``, which is
        reset-atomic) — fine for reset's tests/new-run use."""
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                from hyperspace_tpu.telemetry.histogram import Histogram

                h = self._hists[name] = Histogram()
        h.observe(value)

    def get(self, name: str) -> float:
        """Current counter value (0 if never incremented); gauges via
        :meth:`snapshot`."""
        with self._lock:
            return self._counters.get(name, 0)

    def mark(self) -> dict:
        """Opaque per-run baseline for :meth:`snapshot`: counter values
        plus the gauge write sequence at capture time.  A consumer
        reporting per-run numbers from this process-cumulative registry
        (run_loop in library use) captures one at run start."""
        with self._lock:
            counters = dict(self._counters)
            seq = self._seq
            hists = dict(self._hists)
        # histogram snapshots are taken OUTSIDE the registry lock (each
        # histogram has its own) — same reason observe() releases it
        return {"counters": counters, "seq": seq,
                "hists": {k: h.snapshot() for k, h in hists.items()}}

    def snapshot(self, prefix: str = "", baseline: Optional[dict] = None
                 ) -> dict:
        """One consistent {prefix+name: value} view of every counter and
        gauge — the dict the loop merges into JSONL records.  With a
        ``baseline`` (a prior :meth:`mark`) counters are reported as
        deltas since the capture, and gauges are included only if
        WRITTEN since it — a stale level from a previous in-process run
        (e.g. its ``ckpt/bytes``) never masquerades as this run's.

        Histograms ride along as ``hist/<name>`` entries (count/sum/
        min/max/p50..p99 dicts — :meth:`HistogramSnapshot.fields`).
        They keep the fixed ``hist/`` namespace rather than taking
        ``prefix`` (the loop's ``ctr/`` prefix means "counter"; these
        are not), so JSONL records and bench artifacts carry e.g.
        ``hist/serve/e2e_ms`` verbatim.  With a baseline, each
        histogram is the DELTA distribution since the mark, and
        histograms with no observations since it are omitted — the
        same stale-exclusion contract as gauges."""
        with self._lock:
            if baseline is None:
                out = {prefix + k: v for k, v in self._counters.items()}
                out.update(
                    (prefix + k, v) for k, (v, _s) in self._gauges.items())
            else:
                base_c, base_s = baseline["counters"], baseline["seq"]
                out = {prefix + k: v - base_c.get(k, 0)
                       for k, v in self._counters.items()}
                out.update((prefix + k, v)
                           for k, (v, s) in self._gauges.items()
                           if s > base_s)
            hists = dict(self._hists)
        base_h = (baseline or {}).get("hists", {})
        for name, h in hists.items():
            snap = h.snapshot()
            if baseline is not None:
                prior = base_h.get(name)
                if prior is not None:
                    snap = snap.since(prior)
                if snap.count <= 0:
                    continue
            out["hist/" + name] = snap.fields()
        return out

    def export(self, hist_names=None) -> tuple[dict, dict, dict]:
        """``(counters, gauges, hist_snapshots)`` — the raw state the
        Prometheus exposition (:mod:`hyperspace_tpu.telemetry.
        exposition`) and the SLO window (:mod:`~.window`) render from.
        Unlike :meth:`snapshot`, histograms come back as
        :class:`~hyperspace_tpu.telemetry.histogram.HistogramSnapshot`
        objects (bucket counts included — cumulative ``le`` buckets and
        ring-delta subtraction both need the vector, not the summary
        fields) and gauges lose their write-seq bookkeeping.
        ``hist_names`` (a container) limits which histograms are
        snapshotted — the SLO window captures one histogram per 5 s
        slot and per stats read, and snapshotting every ~285-bucket
        vector only to discard them would tax the admission path."""
        with self._lock:
            counters = dict(self._counters)
            gauges = {k: v for k, (v, _s) in self._gauges.items()}
            hists = dict(self._hists)
        if hist_names is not None:
            hists = {k: h for k, h in hists.items() if k in hist_names}
        # snapshots OUTSIDE the registry lock (each histogram has its
        # own) — the same ordering rule as mark()
        return counters, gauges, {k: h.snapshot() for k, h in hists.items()}

    def reset(self) -> None:
        """Drop every counter/gauge/histogram (tests; a new run
        in-process)."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()
            self._seq = 0


_default: Optional[Registry] = None
_default_lock = threading.Lock()


def default_registry() -> Registry:
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = Registry()
    return _default


def inc(name: str, value: float = 1) -> None:
    """Bump a counter on the default registry (the call sites' one-liner)."""
    default_registry().inc(name, value)


def set_gauge(name: str, value: float) -> None:
    default_registry().set_gauge(name, value)


def observe(name: str, value: float) -> None:
    """Record one value into histogram ``name`` on the default registry
    (latencies in ms by call-site convention — telemetry/histogram.py)."""
    default_registry().observe(name, value)


def snapshot(prefix: str = "") -> dict:
    return default_registry().snapshot(prefix)


_hook_installed = False


def install_jax_monitoring_hook() -> None:
    """Route jax's compile-duration events into the default registry.

    Idempotent (one listener per process — jax.monitoring offers no
    per-listener removal).  The listener resolves ``default_registry()``
    at event time, so a test that swaps/resets the registry still sees
    fresh counts.  Counts ``/jax/core/compile/backend_compile_duration``
    events: one per XLA backend compile, i.e. recompiles once the run's
    steady state is reached.  The persistent-cache counters (module
    docstring) come from the cache's own explicit events: a
    ``cache_hits`` event is an executable deserialized from disk, a
    ``cache_misses`` event a compile the cache could not serve.  NOTE
    on this jax's accounting: a persistent-cache HIT still fires the
    ``backend_compile`` duration event (it times the deserialization),
    so ``jax/recompiles`` counts executable *materializations* either
    way — the cache's win reads in ``jax/compile_s`` collapsing (~20×
    on this image) and in the hit counter, not in a lower recompile
    count.  In-process warm executables fire nothing, so the flat-once-
    warm contracts are unchanged.

    The same events are spans where spans are being recorded
    (``trace.tracing()``): ``jit_trace``, ``jit_lower`` and ``compile``,
    each from its duration back from now, with jax's ``fun_name``; a
    ``compile`` also says what the persistent cache did for it
    (``cache``: ``hit``, ``miss``: asked and compiled, ``off``: not
    asked).
    """
    global _hook_installed
    if _hook_installed:
        return
    import jax.monitoring as _mon

    from hyperspace_tpu.telemetry import trace

    # what the persistent cache said of the compile this thread is in:
    # jax fires the cache's events inside the compile they belong to, on
    # its thread, before the compile's own duration event
    pending = threading.local()

    def _span(name: str, duration: float, kw: dict, **more) -> None:
        if trace.tracing():
            now = time.perf_counter()
            trace.record_span(name, now - duration, now,
                              {"fun_name": kw.get("fun_name"), **more})

    def _on_duration(event: str, duration: float, **kw) -> None:
        if _BACKEND_COMPILE_SUBSTR in event:
            reg = default_registry()
            reg.inc("jax/recompiles")
            reg.inc("jax/compile_s", float(duration))
            cache, pending.cache = getattr(pending, "cache", "off"), "off"
            _span("compile", duration, kw, cache=cache)
        elif event in _JIT_STAGES:
            name, counter = _JIT_STAGES[event]
            # telemetry-catalog: jax/trace_s
            # telemetry-catalog: jax/lower_s
            default_registry().inc(counter, float(duration))
            _span(name, duration, kw)

    def _on_event(event: str, **_kw) -> None:
        if event == _CACHE_REQUEST_EVENT:
            pending.cache = "miss"  # asked; a hit says so next
        elif event == _CACHE_HIT_EVENT:
            default_registry().inc("jax/compile_cache_hit")
            pending.cache = "hit"
        elif event == _CACHE_MISS_EVENT:
            default_registry().inc("jax/compile_cache_miss")

    _mon.register_event_duration_secs_listener(_on_duration)
    _mon.register_event_listener(_on_event)
    _hook_installed = True
