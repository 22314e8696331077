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

Span names in use are cataloged in docs/observability.md; the catalog
lint covers counters only, so keep the doc in step when adding span call
sites.

**The start-up timeline.**  One a process, open from the import of the
package (this module is the first thing ``hyperspace_tpu/__init__.py``
imports) until :func:`close_startup`, which the layer that owns the
entry point calls: ``run_loop`` where its first dispatch returns, the
serve CLI once the server is ready.  While it is open the module-level
:func:`span` / :func:`record_span` record into it as well, whether or
not the tracer is enabled, each record with the span that caused it: a
second, passive sink, the tracer records as ever.  Once it is closed it
is read-only (:func:`startup_report`, the first Chrome dump after the
close) and the disabled path is the one above.
docs/observability.md, "Start-up timeline".
"""

from __future__ import annotations

import collections
import contextlib
import heapq
import json
import os
import sys
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
    """The profiler's view of one enabled span.  Outside a profiler
    session entering it is a flag check.  ``jax.profiler`` is taken from
    ``sys.modules`` and never imported here: before jax is in there is
    no session to show the span in, and a span of the start-up timeline
    must not be the one that pays for jax's import."""
    jp = sys.modules.get("jax.profiler")
    if jp is None:
        return _NULL
    if step_num is not None:
        return jp.StepTraceAnnotation(name.removesuffix(_STEP_SUFFIX),
                                      step_num=step_num)
    return (jp.TraceAnnotation(name, **args) if args
            else jp.TraceAnnotation(name))


class _Span:
    """The recording context manager (one fresh object per span — spans
    nest and cross threads, so no singleton here).  It feeds the tracer
    when that is enabled (``tracer`` is None otherwise) and, through the
    module-level :func:`span`, the start-up timeline while that is open.

    ``args`` is an optional metadata dict carried into the Chrome-trace
    event (batch size, bucket, cache hits, step — docs/observability.md)
    so Perfetto can correlate spans with load.  The dict is held by
    REFERENCE and read at ``__exit__``: a call site may create it with
    what it knows up front and fill in the rest (e.g. cache hits) before
    the span closes.  The profiler's annotation takes what the dict
    holds when the span is made."""

    __slots__ = ("_tracer", "_name", "_t0", "_args", "_ann", "_startup",
                 "_idx")

    def __init__(self, tracer: Optional["Tracer"], name: str, args=None,
                 step_num=None, startup: Optional["_Startup"] = None):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._startup = startup
        self._ann = (_NULL if tracer is None
                     else _annotation(name, args, step_num))

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        if self._startup is not None:
            self._idx = self._startup.begin(self._name, self._t0,
                                            self._args)
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        if self._startup is not None:
            self._startup.end(self._idx, t1)
        if self._tracer is not None:
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
        lifetime.  The default tracer's dump leads with the start-up
        timeline's spans, less those among its own events (the timeline
        itself is never drained: :func:`startup_report` stays).
        """
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
            self._events.clear()
            self._dropped = 0
        if self is _tracer:
            # the process's start-up timeline first, on the clock every
            # span is on
            events = _startup.lead(events) + events
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
    """True when a module-level :func:`span` would record: the default
    tracer is on, or the start-up timeline is open — the guard hot call
    sites use to skip building a span-``args`` dict entirely on the
    disabled path (``span()`` itself is allocation-free when disabled,
    but a caller-built metadata dict would not be)."""
    t = _tracer
    return (t is not None and t.enabled) or _startup_open is not None


def span(name: str, args: Optional[dict] = None,
         step_num: Optional[int] = None):
    """``with span("prep"): ...`` on the default tracer.

    Call sites keep this unconditionally: disabled (the default) it
    returns the shared nullcontext without allocating.  ``args`` is the
    optional metadata dict for the Chrome dump — held by reference, so
    a call site may fill it in before the span exits.  ``step_num``
    marks the span as one step of the run (:data:`_STEP_SUFFIX`).
    While the start-up timeline is open the span is recorded there too,
    tracer or no tracer.
    """
    t = _tracer
    if t is None or not t.enabled:
        if _startup_open is None:
            return _NULL
        t = None
    return _Span(t, name, args, step_num, _startup_open)


def importing(module: str):
    """``with importing("flax"): import flax.linen as nn`` — an ``import``
    span round an import statement that may be the first to bring a heavy
    module in.  Plain spans at the import sites, no hook in the import
    system: a finder on ``sys.meta_path`` read the same times and made
    ``orbax.checkpoint``'s import take 12 to 26 s longer in the
    benchmark's runs on the chip's machine (PERF.md §6, PR 37)."""
    return span("import", {"module": module})


def record_span(name: str, t0: float, t1: float,
                args: Optional[dict] = None) -> None:
    """One completed span from explicit timestamps, into the default
    tracer if it is on and into the start-up timeline if it is open
    (its cause: the innermost span open on the calling thread)."""
    tl = _startup_open
    if tl is not None:
        tl.add(name, t0, t1, args)
    t = _tracer
    if t is not None and t.enabled:
        t._record(name, t0, t1, args)


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


# --- the start-up timeline ----------------------------------------------------

# the timeline's bound, in records (a cell of the benchmark makes one to
# three thousand, most of them flax's eager init); what is over is counted
_STARTUP_MAX = 16384
# what the report carries of the registry, as it stands at the close
_STARTUP_COUNTERS = (
    "jax/recompiles", "jax/compile_s", "jax/trace_s", "jax/lower_s",
    "jax/compile_cache_hit", "jax/compile_cache_miss",
    "prep_cache/hit", "prep_cache/miss")


def _process_start(now: float) -> tuple:
    """(the ``perf_counter`` reading at which the kernel started this
    process, how it was found): the process's age is its start time in
    ``/proc/self/stat`` (ticks since boot) against ``CLOCK_BOOTTIME``;
    where that cannot be read the timeline starts at ``now``, the
    package's import."""
    try:
        with open("/proc/self/stat") as f:
            stat = f.read()
        # the fields after "pid (comm)": comm may hold spaces and ")"
        ticks = int(stat[stat.rindex(")") + 2:].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
        if age >= 0.0:
            return now - age, "process_start"
    except (OSError, ValueError, IndexError, AttributeError):
        pass
    return now, "package_import"


def _self_times(ivs: list) -> list:
    """Each interval's self time: the root's span (``ivs[0]``, which
    covers all others) is shared out so that every instant counts once,
    for the interval open at it that started last.  On one thread that
    is a span's duration less what its children cover; where spans of
    two threads, or two records made after the fact, overlap, the later
    start takes the overlap, so the self times still sum to the root's
    duration."""
    own = [0.0] * len(ivs)
    starts = sorted(range(len(ivs)), key=lambda i: (ivs[i][0], i))
    points = sorted({t for iv in ivs for t in iv})
    heap, k, cur = [], 0, points[0]   # heap top: the latest start
    for p in points:
        while heap and ivs[heap[0][2]][1] <= cur:
            heapq.heappop(heap)
        if heap and p > cur:
            own[heap[0][2]] += p - cur
        while k < len(starts) and ivs[starts[k]][0] <= p:
            i = starts[k]
            heapq.heappush(heap, (-ivs[i][0], -i, i))
            k += 1
        cur = p
    return own


class _Startup:
    """The process's start-up timeline.  A record is ``[name, t0, t1,
    thread, args, parent]``: ``perf_counter`` bounds (``t1`` None while
    the span is open) and the index of the span that caused it, the
    innermost span open on its thread when it began, else the root.
    Record 0 is the root ``startup``, from the process's start to the
    close; record 1 ``pre_program``, from there to the package's import
    (the interpreter and whatever the caller ran first: the benchmark's
    harness imports jax and brings the devices up there)."""

    def __init__(self):
        now = time.perf_counter()
        origin, how = _process_start(now)
        tid = threading.get_ident()
        self.lock = threading.Lock()
        self.closed = False
        self.dropped = 0
        self.records = [
            ["startup", origin, None, tid, {"origin": how}, -1],
            ["pre_program", origin, now, tid,
             {"jax_imported": "jax" in sys.modules}, 0]]
        self.stacks: dict = {}    # thread -> its open spans, innermost last
        self.report: Optional[dict] = None
        self.own: list = []       # self time a record, once closed
        self.dumped = False       # a Chrome dump has led with it, closed

    def _parent(self, tid) -> int:
        stack = self.stacks.get(tid)
        return stack[-1] if stack else 0

    def _room(self) -> bool:
        if self.closed:
            return False
        if len(self.records) >= _STARTUP_MAX:
            self.dropped += 1
            return False
        return True

    def begin(self, name: str, t0: float, args) -> int:
        tid = threading.get_ident()
        with self.lock:
            if not self._room():
                return -1
            self.records.append([name, t0, None, tid, args,
                                 self._parent(tid)])
            idx = len(self.records) - 1
            self.stacks.setdefault(tid, []).append(idx)
        return idx

    def end(self, idx: int, t1: float) -> None:
        """Close record ``idx`` (nothing for a span that was dropped, or
        that the close has ended already)."""
        if idx < 0:
            return
        with self.lock:
            if self.closed:
                return
            rec = self.records[idx]
            rec[2] = t1
            self.stacks[rec[3]].remove(idx)

    def add(self, name: str, t0: float, t1: float, args) -> None:
        tid = threading.get_ident()
        with self.lock:
            if self._room():
                self.records.append([name, t0, t1, tid, args,
                                     self._parent(tid)])

    def close(self, now: float) -> bool:
        with self.lock:
            if self.closed:
                return False
            self.closed = True
            for rec in self.records:
                if rec[2] is None:
                    rec[2] = now   # the root, and whatever is still open
            self.stacks.clear()
        from hyperspace_tpu.telemetry import registry

        reg = registry.default_registry()
        origin = self.records[0][1]
        clamp = lambda t: min(max(t, origin), now)
        ivs = [(clamp(r[1]), clamp(r[2])) for r in self.records]
        self.own = _self_times(ivs)
        spans: dict = {}
        for rec, (a, b), own in zip(self.records[1:], ivs[1:],
                                    self.own[1:]):
            e = spans.setdefault(rec[0],
                                 {"n": 0, "total_s": 0.0, "self_s": 0.0})
            e["n"] += 1
            e["total_s"] += b - a
            e["self_s"] += own
        self.report = {
            "total_s": now - origin,
            "origin": self.records[0][4]["origin"],
            "unattributed_s": self.own[0],
            "spans": spans,
            "counters": {k: reg.get(k) for k in _STARTUP_COUNTERS},
            "dropped": self.dropped}
        return True

    def lead(self, events: list) -> list:
        """The timeline's finished records as Chrome-dump events, less
        those ``events`` (the tracer's own, recorded beside them while
        it was on) already hold: one (name, start, thread) is drawn
        once, and a span the close cut short gives way to the tracer's
        whole one.  The closed timeline leads one dump, the first; a
        dump while it is open (a crash before the close) takes what has
        ended so far and leaves the whole to that first."""
        with self.lock:
            if self.dumped:
                return []
            self.dumped = self.closed
            recs = [tuple(r[:5]) for r in self.records if r[2] is not None]
        seen = {(e[0], e[1], e[3]) for e in events}
        return [r for r in recs if (r[0], r[1], r[3]) not in seen]


_startup = _Startup()
# the timeline while it is open, else None: what span() reads
_startup_open: Optional[_Startup] = _startup


def startup_open() -> bool:
    """Whether the process's start-up timeline is still recording."""
    return _startup_open is not None


def close_startup() -> None:
    """End the start-up timeline now (idempotent; once closed, one
    check): the root ends, spans still open end with it, and the report
    is made.  Called by the layer that owns the entry point, where the
    process is up: ``run_loop`` at its first dispatch's return, the
    serve CLI once the server is ready, ``cli_session`` on its way out
    for whatever got to neither."""
    global _startup_open
    if _startup_open is not None and _startup.close(time.perf_counter()):
        _startup_open = None


def startup_report() -> Optional[dict]:
    """The closed timeline by span name (None while it is open)::

        {"total_s", "origin", "unattributed_s",
         "spans": {name: {"n", "total_s", "self_s"}},
         "counters": {...as they stood at the close...}, "dropped"}

    ``total_s`` runs from the process's start (``origin``:
    ``process_start``, or ``package_import`` where the kernel's reading
    was not to be had) to the close.  ``self_s`` is :func:`_self_times`'
    share, ``unattributed_s`` the root's own, so ``unattributed_s`` +
    every ``self_s`` = ``total_s``; ``total_s`` of a name adds its spans'
    durations, nested ones of one name included."""
    return _startup.report


def startup_fields() -> dict:
    """The report flat, ``startup/...``, for a JSONL record ({} while
    open)."""
    rep = _startup.report
    if rep is None:
        return {}
    out = {"startup/total_s": round(rep["total_s"], 6),
           "startup/unattributed_s": round(rep["unattributed_s"], 6),
           "startup/origin": rep["origin"],
           "startup/dropped": rep["dropped"]}
    for name, e in rep["spans"].items():
        out[f"startup/{name}_s"] = round(e["total_s"], 6)
        out[f"startup/{name}_self_s"] = round(e["self_s"], 6)
        out[f"startup/{name}_n"] = e["n"]
    out.update((f"startup/ctr/{k}", v) for k, v in rep["counters"].items())
    return out


def startup_spans() -> list:
    """The closed timeline record by record ([] while open): ``name``,
    ``start_s`` (from the process's start), ``dur_s``, ``self_s``,
    ``thread``, ``args``, ``parent`` (an index into this list, -1 for
    the root) — ``import`` by module, ``compile`` by ``fun_name`` and
    ``cache`` are read here."""
    if _startup.report is None:
        return []
    origin = _startup.records[0][1]
    return [{"name": r[0], "start_s": r[1] - origin, "dur_s": r[2] - r[1],
             "self_s": own, "thread": r[3], "args": r[4], "parent": r[5]}
            for r, own in zip(_startup.records, _startup.own)]
