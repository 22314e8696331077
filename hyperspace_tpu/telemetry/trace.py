"""Host-level trace spans: ``with span("dispatch"): ...``.

PR 1 made the hot path opaque from the outside: K steps disappear into
one ``lax.scan`` dispatch, and the JSONL stream says nothing about WHERE
wall-clock time went between two log boundaries — host prep, a prefetch
stall, the dispatch itself, or a checkpoint write.  ``train/profiling
.trace`` answers the on-device question (XLA ops, via jax.profiler);
this module answers the host-side one with nested wall-clock spans that

- cost ~nothing when disabled: the module-level :func:`span` returns a
  shared ``nullcontext`` singleton without allocating (one attribute
  check per call — the tested disabled-mode contract), so call sites
  stay unconditionally instrumented;
- aggregate per span name between JSONL log boundaries —
  ``Tracer.flush_fields()`` → ``{"span/<name>_s": seconds, ...}`` —
  one group of fields per log record, no per-span I/O;
- optionally retain every event for a Chrome/Perfetto ``trace_events``
  dump (:meth:`Tracer.dump_chrome_trace`): load the JSON in
  https://ui.perfetto.dev to see the nested host timeline next to the
  numbers the JSONL already carries;
- show in any ``jax.profiler`` session taken while the tracer is on:
  an enabled span also enters a ``jax.profiler.TraceAnnotation`` (a
  ``StepTraceAnnotation`` when it carries ``step_num``), so the
  profile's host plane holds the program's spans on the device's own
  clock, beside the device operations.

Span names in use are cataloged in docs/observability.md
(``train_step``, ``prep``, ``prefetch_wait``, ``dispatch``,
``metrics_flush``, ``ckpt_save``, ``eval``); the catalog lint covers
counters only, but keep the doc in step when adding span call sites.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time
from typing import Optional

# one reusable, stateless disabled-path context manager: entering it is
# a couple of attribute lookups and no allocation
_NULL = contextlib.nullcontext()

# retention cap for the Chrome dump event list — a runaway span loop
# must not eat the host; ~1e6 events ≈ 100 MB JSON, far beyond any
# useful trace.  A ring (deque maxlen): the OLDEST events are evicted,
# because the dump's crash-diagnosis job needs the timeline's TAIL —
# what happened just before the failure (drop count kept for honesty).
_MAX_EVENTS = 1_000_000

# a span that carries ``step_num`` is a step marker: the profiler gets
# ``StepTraceAnnotation(<name less this suffix>, step_num=...)`` — "train"
# for ``train_step``, the name its step views group by
_STEP_SUFFIX = "_step"


def _annotation(name: str, args, step_num):
    """The profiler's view of one enabled span (imported on first use:
    the disabled path never touches jax).  Outside a profiler session
    entering it is a flag check."""
    from jax.profiler import StepTraceAnnotation, TraceAnnotation

    if step_num is not None:
        return StepTraceAnnotation(name.removesuffix(_STEP_SUFFIX),
                                   step_num=step_num)
    return TraceAnnotation(name, **args) if args else TraceAnnotation(name)


class _Span:
    """The enabled-path context manager (one fresh object per span —
    spans nest and cross threads, so no singleton here).

    ``args`` is an optional metadata dict carried into the Chrome-trace
    event (batch size, bucket, cache hits, step — docs/observability.md)
    so Perfetto can correlate spans with load.  The dict is held by
    REFERENCE and read at ``__exit__``: a call site may create it with
    what it knows up front and fill in the rest (e.g. cache hits) before
    the span closes.  The profiler's annotation takes what the dict
    holds when the span is made."""

    __slots__ = ("_tracer", "_name", "_t0", "_args", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args=None,
                 step_num=None):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._ann = _annotation(name, args, step_num)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._tracer._record(self._name, self._t0, t1, self._args)
        return False


class Tracer:
    """Wall-clock span recorder: per-name aggregates (always, when
    enabled) + the full event list (only when ``keep_events``)."""

    def __init__(self, *, enabled: bool = False, keep_events: bool = False):
        self.enabled = enabled
        self.keep_events = keep_events
        self._lock = threading.Lock()
        self._agg: dict[str, float] = {}        # since last flush
        self._agg_n: dict[str, int] = {}
        self._total: dict[str, float] = {}      # run-cumulative
        self._total_n: dict[str, int] = {}
        # (name, t0, t1, tid, args) ring — full, oldest events evict first
        self._events: collections.deque = collections.deque(
            maxlen=_MAX_EVENTS)
        self._dropped = 0

    # --- recording ------------------------------------------------------------

    def span(self, name: str, args: Optional[dict] = None,
             step_num: Optional[int] = None):
        """Context manager timing one ``name`` span; nests freely.
        ``args`` (optional metadata dict) rides into the Chrome dump;
        ``step_num`` makes the span a step marker in a device profile."""
        if not self.enabled:
            return _NULL
        return _Span(self, name, args, step_num)

    def record_span(self, name: str, t0: float, t1: float,
                    args: Optional[dict] = None) -> None:
        """Record one completed span from explicit timestamps — for call
        sites that only know after the fact whether the work really
        happened (e.g. an interval-gated checkpoint save)."""
        self._record(name, t0, t1, args)

    def _record(self, name: str, t0: float, t1: float,
                args: Optional[dict] = None) -> None:
        dur = t1 - t0
        with self._lock:
            self._agg[name] = self._agg.get(name, 0.0) + dur
            self._agg_n[name] = self._agg_n.get(name, 0) + 1
            self._total[name] = self._total.get(name, 0.0) + dur
            self._total_n[name] = self._total_n.get(name, 0) + 1
            if self.keep_events:
                if len(self._events) == self._events.maxlen:
                    self._dropped += 1  # deque evicts the oldest
                self._events.append(
                    (name, t0, t1, threading.get_ident(), args))

    def reset(self) -> None:
        """Drop all aggregates/events (tests; a new run in-process).
        Like the registry, a tracer is otherwise process-cumulative."""
        with self._lock:
            self._agg.clear()
            self._agg_n.clear()
            self._total.clear()
            self._total_n.clear()
            self._events.clear()
            self._dropped = 0

    # --- reading --------------------------------------------------------------

    def flush_fields(self, prefix: str = "span/") -> dict:
        """``{prefix<name>_s: seconds_since_last_flush}`` and reset the
        boundary aggregates (cumulative totals are untouched) — the
        fields a JSONL log record carries for its interval."""
        with self._lock:
            out = {f"{prefix}{k}_s": round(v, 6)
                   for k, v in self._agg.items()}
            self._agg.clear()
            self._agg_n.clear()
        return out

    def total_fields(self, prefix: str = "span/") -> dict:
        """Run-cumulative ``{prefix<name>_s, prefix<name>_n}`` — the
        telemetry_summary payload."""
        with self._lock:
            out = {}
            for k, v in self._total.items():
                out[f"{prefix}{k}_s"] = round(v, 6)
                out[f"{prefix}{k}_n"] = self._total_n[k]
        return out

    # --- Chrome/Perfetto dump -------------------------------------------------

    def dump_chrome_trace(self, path: str) -> int:
        """Write retained events as Chrome ``trace_events`` JSON
        (Perfetto-loadable); returns the number of events written.

        Complete "X" events on one pid, one tid per host thread —
        nesting is by time containment, exactly how the spans nested.
        DRAINS the retained events: a later dump (a second run in the
        same process) starts from a clean timeline and the memory is
        released rather than held to the retention cap for the process
        lifetime.
        """
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
            self._events.clear()
            self._dropped = 0
        pid = os.getpid()
        tids: dict[int, int] = {}
        trace = []
        for name, t0, t1, ident, args in events:
            tid = tids.setdefault(ident, len(tids))
            ev = {
                "name": name, "ph": "X", "pid": pid, "tid": tid,
                "ts": round(t0 * 1e6, 3),
                "dur": round((t1 - t0) * 1e6, 3),
            }
            if args:
                # the optional metadata payload (batch size, bucket,
                # step, cache hits) — Perfetto shows it on click, so a
                # slow span is attributable to its load
                ev["args"] = args
            trace.append(ev)
        doc = {"traceEvents": trace, "displayTimeUnit": "ms",
               "otherData": {"source": "hyperspace_tpu.telemetry",
                             "dropped_events": dropped}}
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
        return len(trace)


_tracer: Optional[Tracer] = None
_tracer_lock = threading.Lock()


def default_tracer() -> Tracer:
    """The process-wide tracer every module-level :func:`span` feeds
    (disabled until :func:`enable` — zero-cost by default)."""
    global _tracer
    if _tracer is None:
        with _tracer_lock:
            if _tracer is None:
                _tracer = Tracer()
    return _tracer


def tracing() -> bool:
    """True when the default tracer is recording — the guard hot call
    sites use to skip building a span-``args`` dict entirely on the
    disabled path (``span()`` itself is allocation-free when disabled,
    but a caller-built metadata dict would not be)."""
    t = _tracer
    return t is not None and t.enabled


def span(name: str, args: Optional[dict] = None,
         step_num: Optional[int] = None):
    """``with span("prep"): ...`` on the default tracer.

    Call sites keep this unconditionally: disabled (the default) it
    returns the shared nullcontext without allocating.  ``args`` is the
    optional metadata dict for the Chrome dump — held by reference, so
    a call site may fill it in before the span exits.  ``step_num``
    marks the span as one step of the run (:data:`_STEP_SUFFIX`).
    """
    t = _tracer
    if t is None or not t.enabled:
        return _NULL
    return _Span(t, name, args, step_num)


def enable(*, keep_events: bool = False) -> Tracer:
    """Turn the default tracer on (``keep_events`` retains the full
    event list for a Chrome dump) and return it.  ``keep_events`` is
    SET, not or-ed: a later run without ``trace_out`` must be able to
    turn retention back off (the CLI and run_loop both derive the flag
    from the same run config, so duplicate enables within one run
    always agree)."""
    t = default_tracer()
    t.enabled = True
    t.keep_events = keep_events
    return t


def disable() -> None:
    t = default_tracer()
    t.enabled = False
