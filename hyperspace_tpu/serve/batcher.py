"""Request micro-batcher: bucket padding + per-query LRU result cache.

The engine compiles one XLA program per (batch size, k); letting raw
request sizes reach it would compile per request — the classic serving
failure where p99 latency is the compiler.  The batcher stands between
requests and the engine:

- **Bucketing.**  Query batches are padded (by repeating the last id —
  always a valid row) up to the smallest power-of-two bucket that fits,
  from ``min_bucket`` to ``max_bucket``; bigger requests are split into
  ``max_bucket`` slabs.  The engine therefore ever sees only
  ``log2(max/min)+1`` distinct batch shapes: compiles happen once per
  (bucket, k) at warmup and never again (``jax/recompiles`` is the
  regression alarm).  Padded slots are real-but-discarded work, counted
  in ``serve/padded_waste`` (with ``serve/slots`` the total dispatched)
  and summarized as the ``serve/padded_waste_ratio`` gauge, so an overly
  sparse bucket ladder shows up in telemetry rather than in a latency
  mystery.  Cache effectiveness is likewise a gauge
  (``serve/cache_hit_rate``) the bench's ``serve_qps`` leg reads.
- **Result cache.**  An LRU keyed ``(artifact fingerprint, query id,
  k)`` holding per-query top-k rows.  The fingerprint key means a
  reloaded (different) artifact can never serve another table's cached
  neighbors; per-ID granularity means a request mixing hot and cold ids
  only computes the cold ones.  ``serve/cache_hit`` / ``serve/cache_miss``
  count per id; edge scoring is uncached (pairs rarely repeat; the
  distance gather is already one cheap dispatch).

Every public entry wraps itself in a ``query`` trace span (carrying an
``args`` payload — op, request/batch sizes, buckets, cache hits — so
Perfetto correlates spans with load) and bumps ``serve/requests`` —
with telemetry enabled (docs/observability.md) a serving process's
JSONL/trace shows the same spans and counters a training run's does.

**Per-request lifecycle** (docs/observability.md "Histograms"): each
request is stamped with monotonic timestamps at enqueue (entry),
batch-form (validation + cache pass done, slabs about to dispatch),
dispatch, and complete, and observes three latency histograms —
``serve/queue_wait_ms`` (enqueue→batch-form: host-side time before any
device work; the name anticipates the async front door, where this
becomes real queueing), ``serve/dispatch_ms`` (engine dispatch + result
fetch, summed over the request's slabs; only observed when at least one
slab actually dispatched), and ``serve/e2e_ms`` (enqueue→complete).
These are what ``bench_serve`` reports p50/p95/p99 per bucket from, and
what the serve CLI's latency summary line reads.

**Overload safety** (docs/resilience.md): with ``queue_max=N`` the
batcher fronts a bounded admission counter — a request arriving while
``N`` are already in flight is SHED with a typed ``overloaded`` error
(``serve/shed``), never queued unboundedly.  Admission occupancy feeds
a hysteresis :class:`~hyperspace_tpu.resilience.degrade.
HysteresisLadder`: under sustained pressure the IVF probe width steps
down toward its floor of 1 (each step counted in ``serve/degraded``,
the level in the ``serve/degrade_level`` gauge), then the batcher
answers **cache-only** (cold ids shed with ``overloaded``); sustained
calm steps back up (``serve/degrade_recovered``).  Per-request
``deadline_ms`` is enforced at three points — after the cache pass,
before each slab dispatch (an expired request is never dispatched
late), and at completion (a result computed past the deadline is
answered ``deadline_exceeded``, not returned as if on time) — counted
in ``serve/deadline_exceeded``.  All of it is **off by default**:
``queue_max=0`` constructs none of the machinery and the hot path
gains two attribute checks.  Failed requests (shed/expired) observe no
latency histograms — ``serve/e2e_ms`` stays the distribution of
honestly answered requests.

Thread-safety: the LRU is lock-guarded; engine dispatches are jax-level
thread-safe; the admission counter and ladder carry their own locks
(concurrent callers — threads today, the async front door next — are
the population admission control exists for; the blocking CLI loop
never sheds).  One batcher serves one engine (one artifact).

**Pipeline stages** (the continuous-batching refactor): ``topk`` is a
composition of four callable stages — :meth:`RequestBatcher.
validate_topk_request` (host-side id/k validation), :meth:`~Request
Batcher.plan_topk` (ladder mode → effective nprobe + the cache key
function), :meth:`~RequestBatcher.cache_pass` (per-unique-id LRU
lookup + hit/miss counters + the cache-only shed), and :meth:`~Request
Batcher.dispatch_topk` (bucket-pad, chaos site, engine call, cache
put) — so the asyncio collator (``serve/collator.py``) can run the
same validation/cache/dispatch code with its OWN queueing between the
cache pass and the dispatch, instead of forking the pipeline.
``dispatch_topk`` takes a ``lives`` sequence: a collated flush
attributes the one shared device dispatch to every participating
request's lifecycle while counting the engine slots exactly once.
``t_enq=`` on the public entries backdates the lifecycle's enqueue
stamp (and therefore the deadline origin) to socket-accept time — in
the HTTP front door, queue time counts against the budget.
"""

from __future__ import annotations

import collections
import operator
import threading
import time
from typing import Optional, Sequence

import jax
import numpy as np

from hyperspace_tpu.kernels import _support as kernel_support
from hyperspace_tpu.resilience import faults
from hyperspace_tpu.serve.access import new_request_id
from hyperspace_tpu.serve.engine import QueryEngine
from hyperspace_tpu.serve.errors import (DeadlineExceededError,
                                         OverloadedError, ServeError,
                                         kind_of)
from hyperspace_tpu.telemetry import registry as telem
from hyperspace_tpu.telemetry import spans
from hyperspace_tpu.telemetry.exposition import tenant_metric
from hyperspace_tpu.telemetry.trace import span, tracing

DEFAULT_MIN_BUCKET = 8
DEFAULT_MAX_BUCKET = 1024
DEFAULT_CACHE_SIZE = 65536
_CACHE_ONLY = "cache_only"  # the ladder's terminal level


def bucket_sizes(min_bucket: int = DEFAULT_MIN_BUCKET,
                 max_bucket: int = DEFAULT_MAX_BUCKET) -> tuple:
    """The power-of-two bucket ladder, smallest to largest."""
    if min_bucket < 1 or max_bucket < min_bucket:
        raise ValueError(f"bad bucket range [{min_bucket}, {max_bucket}]")
    out, b = [], 1
    while b < min_bucket:
        b *= 2
    while b < max_bucket:
        out.append(b)
        b *= 2
    out.append(max_bucket)
    return tuple(out)


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n (callers split requests bigger than the top
    bucket into top-bucket slabs first)."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _checked_ids(ids, name: str, num_nodes: int) -> list[int]:
    """Validate a request's id list on host BEFORE any dtype cast.

    Every id must be integral — a float like 1.9 must fail, never
    silently truncate to another node's answer — and in
    [0, num_nodes), so a huge int can never wrap through the int32
    device cast into a valid-looking id.  Raises ValueError (the serve
    loop's per-line error path)."""
    if isinstance(ids, np.ndarray):
        ids = ids.reshape(-1).tolist()
    elif np.isscalar(ids):
        raise ValueError(f"{name} must be a list of ids")
    if not len(ids):
        raise ValueError(f"{name} must be a non-empty id list")
    out = []
    for i in ids:
        if isinstance(i, bool):  # bools index-coerce to 0/1 — reject
            raise ValueError(f"{name} must be integer ids; got bool")
        try:
            i = operator.index(i)
        except TypeError:
            raise ValueError(
                f"{name} must be integer ids; got "
                f"{type(i).__name__}") from None
        if not 0 <= i < num_nodes:
            raise ValueError(f"{name} id {i} out of range [0, {num_nodes})")
        out.append(i)
    return out


class _LRU:
    """Tiny lock-guarded LRU: (fingerprint, qid, k) -> (idx row, dist row)."""

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self._d: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()

    def get(self, key):
        with self._lock:
            try:
                self._d.move_to_end(key)
                return self._d[key]
            except KeyError:
                return None

    def put(self, key, value) -> None:
        if self.capacity <= 0:
            return
        with self._lock:
            self._d[key] = value
            self._d.move_to_end(key)
            while len(self._d) > self.capacity:
                self._d.popitem(last=False)

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)


class _Lifecycle:
    """One request's lifecycle stamps + the three ``serve/*`` histograms.

    Shared by ``topk`` and ``score`` so the stamping contract (module
    docstring, "Per-request lifecycle") lives in exactly one place:
    construct at enqueue, ``formed()`` once validation + cache pass are
    done, attribute each slab's device work via ``slab()`` +
    ``add_dispatch()`` (the result fetch belongs INSIDE the timed
    window — dispatch is async enqueue, the fetch is the completion
    wait), and ``finish()`` to observe.  ``serve/dispatch_ms`` is only
    observed when a slab actually dispatched, so all-cache-hit requests
    don't pull it toward zero.  ``info`` is the span's ``args`` dict
    (None when tracing is off — the disabled hot path stays
    allocation-free); it is read at span exit, so fields landing after
    ``span()`` entry still make the trace.

    ``t_enq=`` backdates the enqueue stamp (the HTTP front door stamps
    at socket accept, so collator queue time counts against both the
    latency histograms and the deadline); the ``serve/slots`` /
    ``serve/padded_waste`` counters moved to the dispatch helper — a
    collated flush shared by several lifecycles must count its engine
    slots exactly once.
    """

    __slots__ = ("t_enq", "t_form", "info", "buckets_used",
                 "dispatch_s", "t_deadline", "op", "request_id",
                 "flush_id", "cache_hits", "cache_misses", "t_done",
                 "t_coll", "t_result", "span", "tenant")

    def __init__(self, op: str, deadline_ms: Optional[float] = None,
                 t_enq: Optional[float] = None,
                 request_id: Optional[str] = None,
                 tenant: Optional[str] = None):
        self.t_enq = time.perf_counter() if t_enq is None else t_enq
        self.t_form = self.t_enq
        self.op = op
        # tenant this request belongs to (multi-tenant registry —
        # serve/registry.py); None on a single-tenant batcher.  Drives
        # the tenant-labeled metric twins and the access-log field.
        self.tenant = tenant
        # request-tracing fields (docs/observability.md "Live metrics,
        # access log, and the flight recorder"): the id joins the
        # response, the access-log line, the span args, and the
        # collator flush that served the request
        self.request_id = request_id
        self.flush_id: Optional[int] = None
        self.cache_hits = 0
        self.cache_misses = 0
        self.t_done: Optional[float] = None
        self.info: Optional[dict] = {"op": op} if tracing() else None
        if self.info is not None and request_id is not None:
            self.info["request_id"] = request_id
        self.buckets_used: list = []
        self.dispatch_s = 0.0
        # stage boundary stamps (docs/observability.md "Span-level
        # tracing"): t_coll marks collator hand-off (None on the sync
        # path — collate_wait collapses to zero), t_result marks
        # results materialized (serialize = the remainder).  Stages are
        # DIFFERENCES of consecutive stamps, so they sum to e2e exactly
        # by construction.
        self.t_coll: Optional[float] = None
        self.t_result: Optional[float] = None
        # the request's span tree root (None when spans are disabled —
        # the zero-cost default); the serve front door's request
        # envelope, if any, adopts it
        self.span = spans.root(op, request_id)
        if self.span is not None:
            self.span.t0 = self.t_enq  # align the tree to enqueue time
        # absolute expiry on the same monotonic clock as the stamps;
        # None = no deadline (the zero-cost default)
        self.t_deadline = (self.t_enq + deadline_ms / 1e3
                           if deadline_ms else None)

    def formed(self) -> None:
        self.t_form = time.perf_counter()

    def collated(self) -> None:
        """Stamp collator hand-off: host-side work (validation + cache
        pass) done, the request is about to wait for its flush group —
        everything between this and ``formed()`` is collate wait."""
        self.t_coll = time.perf_counter()

    def result_ready(self) -> None:
        """Stamp results materialized: device work (or the collated
        flush) delivered; the remainder to completion is serialize."""
        self.t_result = time.perf_counter()

    def check_deadline(self, where: str) -> None:
        """Raise ``deadline_exceeded`` when the request's budget is
        spent — called after the cache pass, before each slab dispatch
        (never dispatch late), and at completion (never answer a
        result as if it were on time)."""
        if (self.t_deadline is not None
                and time.perf_counter() > self.t_deadline):
            telem.inc("serve/deadline_exceeded")
            if self.tenant:
                telem.inc(tenant_metric("serve/deadline_exceeded",
                                        self.tenant))
            raise DeadlineExceededError(
                f"deadline_ms expired {where} "
                f"({(time.perf_counter() - self.t_enq) * 1e3:.1f} ms "
                "elapsed)")

    def slab(self, bucket: int) -> None:
        self.buckets_used.append(bucket)

    def add_dispatch(self, seconds: float) -> None:
        self.dispatch_s += seconds

    def finish(self) -> None:
        if self.info is not None:
            self.info["buckets"] = self.buckets_used
        self.t_done = time.perf_counter()
        telem.observe("serve/queue_wait_ms", (self.t_form - self.t_enq) * 1e3)
        if self.buckets_used:
            telem.observe("serve/dispatch_ms", self.dispatch_s * 1e3)
        telem.observe("serve/e2e_ms", (self.t_done - self.t_enq) * 1e3)
        if self.tenant:
            # the tenant-labeled twin (exposition renders it as a
            # ``tenant=`` label on the same family): per-tenant SLO
            # windows and the multitenant bench read per-tenant p99
            # from this series while the base keeps the aggregate
            telem.observe(tenant_metric("serve/e2e_ms", self.tenant),
                          (self.t_done - self.t_enq) * 1e3)
        if self.span is not None:
            st = self.stages_ms()
            telem.observe("serve/stage/queue_wait_ms", st["queue_wait"])
            telem.observe("serve/stage/collate_wait_ms", st["collate_wait"])
            telem.observe("serve/stage/dispatch_ms", st["dispatch"])
            telem.observe("serve/stage/serialize_ms", st["serialize"])
            t_coll = self.t_coll if self.t_coll is not None else self.t_form
            t_res = (self.t_result if self.t_result is not None
                     else self.t_done)
            self.span.add("queue_wait", self.t_enq, t_coll)
            self.span.add("collate_wait", t_coll, self.t_form)
            self.span.add("dispatch", self.t_form, t_res)
            self.span.add("serialize", t_res, self.t_done)
            self.span.t1 = self.t_done  # exact close, not close()'s now

    def stages_ms(self) -> dict:
        """The per-stage latency decomposition, in ms: consecutive-
        boundary differences that sum to ``e2e_ms`` exactly.  Computed
        from the stamps with defaults (a sync request has no collate
        wait; a failed request's serialize runs to its error time), so
        the access log carries it for every outcome."""
        end = self.t_done if self.t_done is not None else time.perf_counter()
        t_coll = self.t_coll if self.t_coll is not None else self.t_form
        t_res = self.t_result if self.t_result is not None else end
        return {
            "queue_wait": round((t_coll - self.t_enq) * 1e3, 3),
            "collate_wait": round((self.t_form - t_coll) * 1e3, 3),
            "dispatch": round((t_res - self.t_form) * 1e3, 3),
            "serialize": round((end - t_res) * 1e3, 3),
        }

    def access_record(self, outcome: str, degrade_level: int) -> dict:
        """One structured access-log line's payload (serve/access.py):
        the request id joined to its route, buckets, flush id, latency
        decomposition, cache outcome, degrade level, and taxonomy
        outcome.  Failed requests (no ``finish()``) still carry their
        elapsed time — a 504 must be attributable to the flush that
        missed its deadline."""
        end = self.t_done if self.t_done is not None else time.perf_counter()
        return {
            "request_id": self.request_id,
            "route": self.op,
            "tenant": self.tenant,
            "outcome": outcome,
            "bucket": list(self.buckets_used),
            "flush_id": self.flush_id,
            "queue_wait_ms": round((self.t_form - self.t_enq) * 1e3, 3),
            "dispatch_ms": round(self.dispatch_s * 1e3, 3),
            "e2e_ms": round((end - self.t_enq) * 1e3, 3),
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "degrade_level": degrade_level,
            # the per-stage decomposition (sums to e2e_ms exactly) —
            # what scripts/trace_report.py aggregates
            "stages": self.stages_ms(),
        }


class _Admission:
    """Bounded in-flight counter: the admission queue's whole state.

    ``try_admit`` returns the post-admit pressure in [0, 1) — the share
    of the bound OTHER callers hold, ``(inflight − 1) / queue_max`` —
    or None when full (the caller sheds, observing pressure 1.0).  A
    lone caller therefore exerts ZERO pressure: the blocking CLI loop
    (one request in flight, ever) can never walk the ladder down,
    whatever ``queue_max`` is — only genuine concurrency can."""

    def __init__(self, queue_max: int):
        self.queue_max = int(queue_max)
        self.inflight = 0
        self._lock = threading.Lock()

    def try_admit(self) -> Optional[float]:
        with self._lock:
            if self.inflight >= self.queue_max:
                return None
            self.inflight += 1
            return (self.inflight - 1) / self.queue_max

    def release(self) -> None:
        with self._lock:
            self.inflight -= 1


def _ladder_modes(engine: QueryEngine) -> list:
    """Quality modes best-first: full (None), IVF probe widths halving
    toward the floor of 1, then cache-only (docs/resilience.md
    "Degradation ladder")."""
    modes: list = [None]
    if engine.scan_strategy == "ivf":
        p = engine.nprobe // 2
        while p >= 1:
            modes.append(p)
            p //= 2
    modes.append(_CACHE_ONLY)
    return modes


class RequestBatcher:
    """Pads requests onto the bucket ladder and fronts the LRU cache.

    ``queue_max=N`` turns on overload safety (module docstring): the
    bounded admission counter, the degradation ladder (its hysteresis
    knobs ``ladder_high``/``ladder_low``/``ladder_down_after``/
    ``ladder_up_after`` — resilience/degrade.py), and per-request
    deadlines (``deadline_ms=`` here is the default applied when a
    request carries none; requests may override per call).  The
    default ``queue_max=0`` constructs none of it."""

    def __init__(self, engine: QueryEngine, *,
                 min_bucket: int = DEFAULT_MIN_BUCKET,
                 max_bucket: int = DEFAULT_MAX_BUCKET,
                 cache_size: int = DEFAULT_CACHE_SIZE,
                 queue_max: int = 0,
                 deadline_ms: float = 0.0,
                 ladder_high: float = 0.75, ladder_low: float = 0.25,
                 ladder_down_after: int = 1, ladder_up_after: int = 8,
                 window=None, slo_ms: float = 0.0,
                 access_sink=None, recorder=None, slow_sink=None,
                 tenant: Optional[str] = None):
        self.engine = engine
        # multi-tenant identity (serve/registry.py): when set, the key
        # serve series (requests/e2e/shed/deadline/errors) double-write
        # a ``<name>@tenant=<t>`` twin the exposition renders as a
        # tenant label, and access records carry the tenant field.
        # None (the single-tenant default) adds nothing to the hot path.
        self.tenant = tenant
        self.buckets = bucket_sizes(min_bucket, max_bucket)
        self.cache = _LRU(cache_size)
        if queue_max < 0:
            raise ValueError(f"queue_max must be >= 0; got {queue_max}")
        if deadline_ms < 0:
            raise ValueError(
                f"deadline_ms must be >= 0; got {deadline_ms}")
        if slo_ms < 0:
            raise ValueError(f"slo_ms must be >= 0; got {slo_ms}")
        self.default_deadline_ms = float(deadline_ms) or None
        # --- observability plane (docs/observability.md "Live metrics,
        # access log, and the flight recorder"), all None/0 = off at
        # zero cost: `window` is a telemetry.window.SloWindow (ticked
        # per completed request; surfaces in stats()), `slo_ms` arms
        # the ladder's latency-aware pressure signal, `access_sink` is
        # a callable taking one access record (serve.access.AccessLog.
        # emit), `recorder` a FlightRecorder fed degrade transitions,
        # `slow_sink` the slow-query log — a second record sink fed
        # only by requests breaching slo_ms, each carrying its span
        # tree when spans are enabled
        self.window = window
        self.slo_ms = float(slo_ms)
        self.access_sink = access_sink
        self.recorder = recorder
        self.slow_sink = slow_sink
        self._admission = None
        self._ladder = None
        self._modes: list = [None]
        if queue_max > 0:
            from hyperspace_tpu.resilience.degrade import HysteresisLadder

            self._admission = _Admission(queue_max)
            self._modes = _ladder_modes(engine)
            self._ladder = HysteresisLadder(
                len(self._modes), high=ladder_high, low=ladder_low,
                down_after=ladder_down_after, up_after=ladder_up_after,
                on_change=self._on_ladder_change)

    def _on_ladder_change(self, old: int, new: int) -> None:
        if new > old:
            telem.inc("serve/degraded")
        else:
            telem.inc("serve/degrade_recovered")
        telem.set_gauge("serve/degrade_level", new)
        if self.recorder is not None:
            # a degrade transition is an incident trigger: the flight
            # recorder dumps the ring so the storm that caused it (or
            # the interval a recovery closes) leaves evidence
            self.recorder.note_degrade(old, new)

    def _admit(self) -> None:
        """Admission gate: shed with ``overloaded`` when the bounded
        queue is full; feed the ladder the post-admit occupancy — or,
        with ``slo_ms`` + a window armed, the latency pressure when it
        is the worse signal (a server slow without queueing must still
        walk the ladder down)."""
        if self._admission is None:
            return
        occ = self._admission.try_admit()
        if occ is None:
            # serve/shed ticks in emit_access (every overloaded answer
            # is a shed — admission, cache-only, drain alike; counting
            # here too would double-count this path)
            self._ladder.observe(1.0)
            raise OverloadedError(
                "admission queue full "
                f"(queue_max={self._admission.queue_max})")
        if self.window is not None and self.slo_ms > 0:
            occ = max(occ, self.window.latency_pressure(self.slo_ms))
        self._ladder.observe(occ)

    def _release(self) -> None:
        if self._admission is not None:
            self._admission.release()

    def count_request(self) -> None:
        """Bump ``serve/requests`` (+ the tenant twin) — the ONE place
        a request is counted, shared with the collator's async paths so
        a multi-tenant batcher's per-tenant rate can never drift from
        the aggregate."""
        telem.inc("serve/requests")
        if self.tenant:
            telem.inc(tenant_metric("serve/requests", self.tenant))

    def new_lifecycle(self, op: str, deadline_ms: Optional[float] = None,
                      t_enq: Optional[float] = None,
                      request_id: Optional[str] = None) -> "_Lifecycle":
        """A lifecycle stamped with this batcher's tenant (the collator
        constructs lifecycles for its async members through this, so
        tenant threading has one home)."""
        return _Lifecycle(op, deadline_ms, t_enq=t_enq,
                          request_id=request_id, tenant=self.tenant)

    def emit_access(self, life: _Lifecycle, outcome: str = "ok") -> None:
        """One request is DONE (any outcome): tick the SLO window,
        count taxonomy errors (parse/validation/internal — shed and
        deadline keep their own counters, so the window's three rates
        never double-count), and emit the access record when a sink is
        armed.  Shared by the sync paths here and the collator — the
        record-assembly contract lives once."""
        if self.window is not None:
            self.window.tick()
        if outcome == "overloaded":
            # EVERY overloaded answer is a shed — the admission queue,
            # cache-only degradation misses, drain refusals, degraded
            # under-filled probes.  Counting only the admission site
            # left the window's shed_rate reading 0.0 during exactly
            # the cache-only state degradation exists to expose; every
            # overloaded outcome funnels through here exactly once.
            telem.inc("serve/shed")
            if self.tenant:
                telem.inc(tenant_metric("serve/shed", self.tenant))
        elif outcome not in ("ok", "deadline_exceeded"):
            telem.inc("serve/errors")
            if self.tenant:
                telem.inc(tenant_metric("serve/errors", self.tenant))
        if life.span is not None:
            life.span.close()  # failed requests: stamp end at emit time
        breach = False
        if self.slo_ms > 0:
            end = (life.t_done if life.t_done is not None
                   else time.perf_counter())
            breach = (end - life.t_enq) * 1e3 > self.slo_ms
            if breach:
                telem.inc("serve/slow_queries")
        if self.access_sink is None and self.slow_sink is None:
            return
        level = self._ladder.level if self._ladder is not None else 0
        rec = life.access_record(outcome, level)
        if life.span is not None and (outcome != "ok" or breach):
            # incident/slow evidence: the full span tree rides the
            # record — the flight recorder's trigger and the slow-query
            # log read it; healthy fast requests stay one flat line
            rec["span"] = life.span.to_dict()
        if self.access_sink is not None:
            try:
                self.access_sink(rec)
            except OSError:
                pass  # a full disk is evidence loss, never a request failure
        if breach and self.slow_sink is not None:
            try:
                self.slow_sink(rec)
            except OSError:
                pass  # same policy as the access sink

    def emit_synthetic_access(self, op: str, *,
                              request_id: Optional[str] = None,
                              outcome: str = "ok",
                              t_enq: Optional[float] = None) -> None:
        """Access-account a request that never got a real lifecycle —
        the serving surfaces' entry point for failures upstream of the
        batcher (HTTP framing/parse/route errors, stdin pre-dispatch
        failures).  With a sink armed and no id, one is generated (a
        record is never anonymous).  Keeping this here — rather than
        having both surfaces construct bare ``_Lifecycle`` objects —
        pins the synthetic-record contract to the class that owns the
        real one."""
        if request_id is None and self.access_sink is not None:
            request_id = new_request_id()
        self.emit_access(self.new_lifecycle(op, t_enq=t_enq,
                                            request_id=request_id),
                         outcome)

    def _mode(self):
        """Current quality mode: ``None`` (full), an int nprobe
        override, or ``"cache_only"``."""
        if self._ladder is None:
            return None
        return self._modes[self._ladder.level]

    @property
    def degrade_level(self) -> int:
        """Current degradation-ladder level (0 = full quality, also
        when no ladder is armed) — the healthz/access-log field."""
        return self._ladder.level if self._ladder is not None else 0

    # --- startup prewarm (docs/serving.md "Warm starts") ----------------------

    def prewarm(self, ks: Sequence[int], *, buckets=None,
                exclude_self=(True, False)) -> dict:
        """Compile every (bucket, k, exclude_self, ladder-nprobe)
        executable BEFORE traffic, so the first real request on every
        bucket of the ladder is warm — BOTH ``exclude_self`` settings
        by default, since every serving surface accepts the request
        flag and a cold variant would re-open the p99 cliff for
        whichever flavor the warmup skipped — the cold-bucket p99 cliff the
        PR 7 histograms exposed, closed at startup instead of papered
        over by bench warmup.  With the persistent compilation cache on
        (hyperspace_tpu/compile_cache.py) a restarted server's prewarm
        is deserialization, not compilation — this is the blue-green
        warm path ROADMAP item 4 flips onto.

        Dispatches go STRAIGHT to the engine: no LRU writes, no
        request counters, no latency histograms — prewarm traffic must
        never masquerade as served requests (the only registry marks
        are ``serve/prewarmed`` — programs warmed — and
        ``serve/prewarm_s``).  The engine's own scan mode / precision /
        index are baked into its executables, so a prewarmed bf16 or
        fused or probing engine is warm for exactly the signature it
        serves (the batcher cache key's isolation contract, upheld by
        construction).  The IVF degradation ladder's narrowed widths
        (``_ladder_modes``) are warmed too — stepping down under
        pressure must not hand the compiler a fresh program mid-storm.

        ``ks`` are validated against the table like any request's k; an
        IVF probe combination the index cannot fill raises AFTER its
        executable compiled — those are swallowed here (the program is
        warm, which is all prewarm promises).  Returns
        ``{programs, seconds, buckets, ks}``.
        """
        import jax

        eng = self.engine
        ks = sorted({int(k) for k in ks})
        limit = eng.num_nodes - (1 if any(exclude_self) else 0)
        for k in ks:
            if not 1 <= k <= limit:
                raise ValueError(
                    f"prewarm k={k} out of range [1, {limit}] for a "
                    f"{eng.num_nodes}-row table")
        # full width (None) plus every ladder override the degradation
        # path can serve — deduped after the plan_topk clamp rule
        widths: list = [None]
        for m in self._modes:
            if isinstance(m, int) and m not in widths:
                widths.append(m)
        buckets = tuple(buckets or self.buckets)
        t0 = time.perf_counter()
        warmed = 0
        for b in buckets:
            q = np.arange(b, dtype=np.int64) % eng.num_nodes
            for k in ks:
                for ex in exclude_self:
                    seen_p = set()
                    for p in widths:
                        if p is not None:
                            # the ladder's clamp: the narrowed probe
                            # must still hold k rows (plan_topk)
                            mc = eng.index.max_cell
                            p = min(max(p, -(-k // mc)), eng.nprobe)
                            if p >= eng.nprobe or p in seen_p:
                                continue
                            seen_p.add(p)
                        try:
                            out = eng.topk_neighbors(
                                q, k, exclude_self=bool(ex), nprobe=p)
                            jax.block_until_ready(out)
                        except ValueError:
                            # an under-filled probe raises on the
                            # RESULTS — the executable is already warm,
                            # which is all prewarm promises; real
                            # traffic answers the same error per
                            # request
                            pass
                        warmed += 1
        dt = time.perf_counter() - t0
        telem.inc("serve/prewarmed", warmed)
        telem.inc("serve/prewarm_s", dt)
        return {"programs": warmed, "seconds": dt,
                "buckets": list(buckets), "ks": ks}

    # --- pipeline stages (module docstring, "Pipeline stages") ---------------

    def validate_topk_request(self, ids, k) -> tuple[list[int], int]:
        """Host-side request validation: the id list and k, reject-
        don't-coerce (same policy notes as :func:`_checked_ids`)."""
        ids = _checked_ids(ids, "ids", self.engine.num_nodes)
        if isinstance(k, bool):  # True would index-coerce to k=1
            raise ValueError("k must be an integer; got bool")
        try:  # same reject-don't-truncate policy as the ids
            k = operator.index(k)
        except TypeError:
            raise ValueError(
                f"k must be an integer; got {type(k).__name__}") from None
        return ids, k

    def plan_topk(self, k: int, exclude_self: bool):
        """``(keyf, nprobe_ov, cache_only)``: the ladder's current
        quality mode resolved into an effective nprobe override (or
        None = full width) and the cache key function for this
        (k, exclude_self) under that mode."""
        mode = self._mode()
        nprobe_ov = None
        if isinstance(mode, int):
            # degraded probe width, clamped so the narrowed
            # probe can still hold k rows (capacity = p×max_cell)
            mc = self.engine.index.max_cell
            nprobe_ov = min(max(mode, -(-k // mc)), self.engine.nprobe)
            if nprobe_ov >= self.engine.nprobe:
                nprobe_ov = None  # clamped back to full width
        fp = self.engine.fingerprint
        # cache keys carry exclude_self, the engine's precision
        # mode, AND the EFFECTIVE scan signature (("exact",) or
        # ("ivf", nprobe, index fingerprint) — the ladder's
        # narrowed width included): the same (fp, id, k) has
        # distinct answers per flag, a bf16-scan engine's rows
        # must never be served back by an f32 engine over the
        # same table (same fingerprint!), and an approximate
        # probed answer must never be served back as an exact
        # one — or at a different width, through a different
        # index, or vice versa
        prec = self.engine.precision
        scan = (self.engine.scan_signature_for(nprobe_ov)
                if nprobe_ov is not None
                else self.engine.scan_signature)
        keyf = lambda qid: (fp, qid, k, exclude_self, prec, scan)
        return keyf, nprobe_ov, mode == _CACHE_ONLY

    def cache_pass(self, ids: Sequence[int], keyf,
                   cache_only: bool) -> tuple[dict, list[int]]:
        """``(rows, misses)`` over the request's UNIQUE ids — a
        duplicate within the request is one compute (and one counter
        event), hot or cold.  Under cache-only degradation a cold id
        is shed (NOT counted as a cache miss — nothing was computed)
        rather than dispatched."""
        rows: dict[int, tuple] = {}
        misses: list[int] = []
        for qid in dict.fromkeys(ids):
            hit = self.cache.get(keyf(qid))
            if hit is not None:
                rows[qid] = hit
            else:
                misses.append(qid)
        telem.inc("serve/cache_hit", len(rows))
        if cache_only and misses:
            raise OverloadedError(
                f"cache-only degradation: {len(misses)} cold "
                "id(s) in the request")
        telem.inc("serve/cache_miss", len(misses))
        return rows, misses

    def dispatch_topk(self, misses: Sequence[int], k: int, *,
                      exclude_self: bool, nprobe_ov, keyf,
                      lives: Sequence[_Lifecycle],
                      deadline_life: Optional[_Lifecycle] = None,
                      span_parent=None) -> dict:
        """Dispatch ``misses`` through the engine in bucket-padded
        slabs; returns ``{qid: (idx row, dist row)}`` (rows also land
        in the LRU).  The one device dispatch is attributed to EVERY
        lifecycle in ``lives`` (a collated flush shares it) while the
        ``serve/slots``/``serve/padded_waste`` counters count each slab
        once.  ``deadline_life`` (the sync path's own request) enforces
        the before-dispatch deadline check per slab — an expired
        request is never dispatched late; a collated flush checks
        expiry per member at flush time instead, so one member's
        deadline cannot fail the whole batch.  ``span_parent`` scopes
        the engine's ``device_compute``/``rescore`` stages under the
        caller's span (the sync path passes its lifecycle span; the
        collator passes the shared flush span — contextvars don't
        cross its executor boundary on their own)."""
        rows: dict[int, tuple] = {}
        with spans.use(span_parent):
            rows.update(self._dispatch_topk_slabs(
                misses, k, exclude_self=exclude_self, nprobe_ov=nprobe_ov,
                keyf=keyf, lives=lives, deadline_life=deadline_life))
        self._update_gauges()
        return rows

    def _dispatch_topk_slabs(self, misses, k, *, exclude_self, nprobe_ov,
                             keyf, lives, deadline_life):
        rows: dict[int, tuple] = {}
        for s in range(0, len(misses), self.buckets[-1]):
            if deadline_life is not None:
                # the engine call is the unrecallable cost
                deadline_life.check_deadline("before dispatch")
            slab = list(misses[s : s + self.buckets[-1]])
            b = bucket_for(len(slab), self.buckets)
            telem.inc("serve/slots", b)
            telem.inc("serve/padded_waste", b - len(slab))
            for life in lives:
                life.slab(b)
            padded = slab + [slab[-1]] * (b - len(slab))
            if faults.active():
                faults.hit("serve.dispatch")  # chaos site
            t0 = time.perf_counter()
            try:
                idx, dist = self.engine.topk_neighbors(
                    np.asarray(padded, np.int32), k,
                    exclude_self=exclude_self, nprobe=nprobe_ov)
            except ValueError as e:
                if (nprobe_ov is not None
                        and "under-filled" in str(e)):
                    # the SERVER narrowed the probe, not the
                    # client: a width that under-fills at the
                    # degraded level is an overload symptom,
                    # never a fix-your-request validation error
                    raise OverloadedError(
                        f"degraded probe width {nprobe_ov} "
                        f"under-filled for k={k}; retry later"
                    ) from e
                raise
            # the "rescore" stage: forcing the dispatched program's
            # results to host arrays — on the fused lanes the f32
            # rescore itself runs inside the device_compute program,
            # so this window is the completion wait + materialization
            with spans.stage("rescore", metric="serve/stage/rescore_ms"):
                idx = np.asarray(idx)
                dist = np.asarray(dist)
            dt = time.perf_counter() - t0
            for life in lives:
                life.add_dispatch(dt)
            for j, qid in enumerate(slab):
                val = (idx[j].copy(), dist[j].copy())
                rows[qid] = val
                self.cache.put(keyf(qid), val)
        return rows

    # --- top-k ----------------------------------------------------------------

    def topk(self, ids, k: int, *, exclude_self: bool = True,
             deadline_ms: Optional[float] = None,
             t_enq: Optional[float] = None,
             request_id: Optional[str] = None
             ) -> tuple[np.ndarray, np.ndarray]:
        """``(neighbors [B, k] int32, dists [B, k] float)`` in request
        order; cache-aware, bucket-padded.  ``deadline_ms`` overrides
        the batcher default for this request (None = the default;
        module docstring, "Overload safety"); ``t_enq`` backdates the
        enqueue stamp to an earlier ``time.perf_counter()`` reading
        (socket-accept time — queue time counts against the deadline).
        ``request_id`` threads the caller's trace id into the span args
        and the access log; with a sink armed and no id given, one is
        generated — an access-log line is never anonymous."""
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        if request_id is None and self.access_sink is not None:
            request_id = new_request_id()
        life = self.new_lifecycle("topk", deadline_ms, t_enq=t_enq,
                                  request_id=request_id)
        self.count_request()
        try:
            self._admit()
        except OverloadedError:
            # shed at admission: not admitted, so no _release — but the
            # shed IS a taxonomy outcome the access log must carry
            self.emit_access(life, "overloaded")
            raise
        try:
            with span("query", args=life.info):
                ids, k = self.validate_topk_request(ids, k)
                keyf, nprobe_ov, cache_only = self.plan_topk(
                    k, exclude_self)
                rows, misses = self.cache_pass(ids, keyf, cache_only)
                life.cache_hits = len(rows)
                life.cache_misses = len(misses)
                # batch-form stamp: validation + cache pass done, device
                # work (if any) starts now
                life.formed()
                life.check_deadline("after the cache pass")
                if life.info is not None:
                    life.info.update(requests=len(ids), k=k,
                                     cache_hits=len(rows),
                                     cache_misses=len(misses))
                rows.update(self.dispatch_topk(
                    misses, k, exclude_self=exclude_self,
                    nprobe_ov=nprobe_ov, keyf=keyf, lives=(life,),
                    deadline_life=life, span_parent=life.span))
                life.result_ready()
                out_i = np.stack([rows[qid][0] for qid in ids])
                out_d = np.stack([rows[qid][1] for qid in ids])
                # a result computed past the deadline is answered
                # deadline_exceeded, never returned as if on time (the
                # rows stay cached — the work is not wasted)
                life.check_deadline("at completion")
                life.finish()
                self.emit_access(life)
                return out_i, out_d
        except (ServeError, ValueError, KeyError, TypeError,
                OverflowError, OSError) as e:
            # kind_of is the one exception->taxonomy classification
            # (serve/errors.py): the access-log outcome can never
            # diverge from the wire response's kind
            self.emit_access(life, kind_of(e))
            raise
        finally:
            self._release()

    # --- edge scores ----------------------------------------------------------

    def validate_score_request(self, u_ids,
                               v_ids) -> tuple[np.ndarray, np.ndarray]:
        """Host-side score validation: matching int id arrays."""
        n = self.engine.num_nodes
        u = np.asarray(_checked_ids(u_ids, "u", n), np.int64)
        v = np.asarray(_checked_ids(v_ids, "v", n), np.int64)
        if u.shape != v.shape:
            raise ValueError(
                f"score: need matching id lists; got "
                f"{u.shape} vs {v.shape}")
        return u, v

    def dispatch_score(self, u: np.ndarray, v: np.ndarray, *,
                       prob: bool, fd_r: float, fd_t: float,
                       lives: Sequence[_Lifecycle],
                       deadline_life: Optional[_Lifecycle] = None,
                       span_parent=None) -> np.ndarray:
        """Slab-dispatch validated edge pairs (the score analog of
        :meth:`dispatch_topk`; same slot-counting, lifecycle-
        attribution, and span-scoping contract)."""
        out = np.empty((u.size,), np.float64)
        top = self.buckets[-1]
        with spans.use(span_parent):
            for s in range(0, u.size, top):
                if deadline_life is not None:
                    deadline_life.check_deadline("before dispatch")
                su, sv = u[s : s + top], v[s : s + top]
                b = bucket_for(su.size, self.buckets)
                telem.inc("serve/slots", b)
                telem.inc("serve/padded_waste", b - su.size)
                for life in lives:
                    life.slab(b)
                pu = np.concatenate([su, np.full(b - su.size, su[-1])])
                pv = np.concatenate([sv, np.full(b - sv.size, sv[-1])])
                if faults.active():
                    faults.hit("serve.dispatch")  # chaos site
                t0 = time.perf_counter()
                d = self.engine.score_edges(
                    pu.astype(np.int32), pv.astype(np.int32),
                    prob=prob, fd_r=fd_r, fd_t=fd_t)
                with spans.stage("rescore",
                                 metric="serve/stage/rescore_ms"):
                    out[s : s + su.size] = np.asarray(d)[: su.size]
                dt = time.perf_counter() - t0
                for life in lives:
                    life.add_dispatch(dt)
        self._update_gauges()
        return out

    def score(self, u_ids, v_ids, *, prob: bool = False,
              fd_r: float = 2.0, fd_t: float = 1.0,
              deadline_ms: Optional[float] = None,
              t_enq: Optional[float] = None,
              request_id: Optional[str] = None) -> np.ndarray:
        """Bucket-padded ``engine.score_edges`` ([B] in request order).

        Same admission/deadline/request-id contract as :meth:`topk`;
        edge scoring is uncached, so the cache-only degradation level
        sheds every score request (an uncached op has nothing cheaper
        to serve)."""
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        if request_id is None and self.access_sink is not None:
            request_id = new_request_id()
        life = self.new_lifecycle("score", deadline_ms, t_enq=t_enq,
                                  request_id=request_id)
        self.count_request()
        try:
            self._admit()
        except OverloadedError:
            self.emit_access(life, "overloaded")
            raise
        try:
            with span("query", args=life.info):
                if self._mode() == _CACHE_ONLY:
                    raise OverloadedError(
                        "cache-only degradation: edge scoring is "
                        "uncached")
                u, v = self.validate_score_request(u_ids, v_ids)
                life.formed()
                life.check_deadline("after validation")
                if life.info is not None:
                    life.info["requests"] = int(u.size)
                out = self.dispatch_score(u, v, prob=prob, fd_r=fd_r,
                                          fd_t=fd_t, lives=(life,),
                                          deadline_life=life,
                                          span_parent=life.span)
                life.result_ready()
                life.check_deadline("at completion")
                life.finish()
                self.emit_access(life)
                return out
        except (ServeError, ValueError, KeyError, TypeError,
                OverflowError, OSError) as e:
            # kind_of is the one exception->taxonomy classification
            # (serve/errors.py): the access-log outcome can never
            # diverge from the wire response's kind
            self.emit_access(life, kind_of(e))
            raise
        finally:
            self._release()

    # --- mutations (live engines only — serve/delta.py) -----------------------

    def _live_engine(self):
        """The engine, checked mutable: a frozen engine answering an
        upsert with an AttributeError deep in the stack would classify
        ``internal`` — it is a validation failure (fix your request /
        serve with ``live=true``), and must say so."""
        if not hasattr(self.engine, "upsert"):
            raise ValueError(
                "engine is frozen: mutations need a live engine "
                "(serve with live=true, or wrap the base in "
                "serve.delta.LiveQueryEngine)")
        return self.engine

    def _mutate(self, op: str, apply, *, deadline_ms: Optional[float],
                t_enq: Optional[float],
                request_id: Optional[str]) -> dict:
        """The shared mutation envelope: same admission / deadline /
        access-log contract as :meth:`topk`; ``apply(engine)`` runs the
        validated mutation and returns the response dict.  On success
        the event→servable freshness (``serve/upsert_visible_ms``:
        enqueue stamp → generation bumped, mask uploaded on next sync)
        is observed — THE latency a live index is judged by."""
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        if request_id is None and self.access_sink is not None:
            request_id = new_request_id()
        life = self.new_lifecycle(op, deadline_ms, t_enq=t_enq,
                                  request_id=request_id)
        self.count_request()
        try:
            self._admit()
        except OverloadedError:
            self.emit_access(life, "overloaded")
            raise
        try:
            with span("query", args=life.info):
                eng = self._live_engine()
                life.formed()
                life.check_deadline("before the mutation")
                out = apply(eng)
                life.result_ready()
                telem.observe("serve/upsert_visible_ms",
                              (time.perf_counter() - life.t_enq) * 1e3)
                # a mutation is never rolled back by its deadline: once
                # applied it is visible (the generation already moved),
                # so the late answer reports deadline_exceeded WITH the
                # mutation durable — like a cached row computed late
                life.check_deadline("at completion")
                life.finish()
                self.emit_access(life)
                return out
        except (ServeError, ValueError, KeyError, TypeError,
                OverflowError, OSError) as e:
            self.emit_access(life, kind_of(e))
            raise
        finally:
            self._release()

    def upsert(self, ids, rows, *,
               deadline_ms: Optional[float] = None,
               t_enq: Optional[float] = None,
               request_id: Optional[str] = None) -> dict:
        """Insert/update rows through the live engine's delta segment
        (``{"upserted", "inserted", "generation", "segment_rows"}``).
        Validation (id contiguity for inserts, row shapes,
        last-write-wins dedup) lives in
        :meth:`~hyperspace_tpu.serve.delta.LiveQueryEngine.upsert`."""
        return self._mutate(
            "upsert", lambda eng: eng.upsert(ids, rows),
            deadline_ms=deadline_ms, t_enq=t_enq, request_id=request_id)

    def delete(self, ids, *,
               deadline_ms: Optional[float] = None,
               t_enq: Optional[float] = None,
               request_id: Optional[str] = None) -> dict:
        """Tombstone rows (``{"deleted", "generation"}``) — the id
        space never shrinks; the rows become unreachable."""
        return self._mutate(
            "delete", lambda eng: eng.delete(ids),
            deadline_ms=deadline_ms, t_enq=t_enq, request_id=request_id)

    # --- introspection --------------------------------------------------------

    def _update_gauges(self) -> None:
        """Refresh the ratio gauges from the cumulative counters.

        The raw ``serve/padded_waste`` counter grows forever; the gauge
        forms (waste / engine slots dispatched, cache hits / lookups)
        are the levels a dashboard — and the bench's ``serve_qps`` leg —
        can read directly without differencing counters."""
        reg = telem.default_registry()
        slots = reg.get("serve/slots")
        if slots:
            telem.set_gauge("serve/padded_waste_ratio",
                            round(reg.get("serve/padded_waste") / slots, 4))
        lookups = reg.get("serve/cache_hit") + reg.get("serve/cache_miss")
        if lookups:
            telem.set_gauge("serve/cache_hit_rate",
                            round(reg.get("serve/cache_hit") / lookups, 4))

    def stats(self) -> dict:
        """Current serve counters + ratio gauges + cache occupancy (the
        `stats` op of the CLI loop).  ``latency_e2e_ms`` is the
        process-cumulative ``serve/e2e_ms`` histogram summary
        (count/sum/min/max/p50..p99) — None before the first request."""
        reg = telem.default_registry()
        gauges = reg.snapshot()
        return {
            "tenant": self.tenant,
            "latency_e2e_ms": gauges.get("hist/serve/e2e_ms"),
            # compile count beside the serve stats (the stdin loop's
            # analog of the HTTP stats field): the contract every smoke
            # and bench leg reads is recompiles FLAT once warm
            "recompiles": reg.get("jax/recompiles"),
            "prewarmed": reg.get("serve/prewarmed"),
            "requests": reg.get("serve/requests"),
            "cache_hit": reg.get("serve/cache_hit"),
            "cache_miss": reg.get("serve/cache_miss"),
            "cache_hit_rate": gauges.get("serve/cache_hit_rate", 0.0),
            "padded_waste": reg.get("serve/padded_waste"),
            "padded_waste_ratio": gauges.get("serve/padded_waste_ratio", 0.0),
            "slots": reg.get("serve/slots"),
            "cache_entries": len(self.cache),
            "buckets": list(self.buckets),
            "fingerprint": self.engine.fingerprint,
            "precision": self.engine.precision,
            # which engine answered: "exact" or "ivf" (+ nprobe) — the
            # serve CLI stats line must identify an approximate server
            "scan_strategy": self.engine.scan_strategy,
            "scan_mode": self.engine.scan_mode,
            "nprobe": self.engine.nprobe,
            # what the programs run on and as: the backend jax resolved
            # and the kernel implementation that follows from it — a
            # server whose kernels run as their XLA twins says so
            "backend": jax.default_backend(),
            "kernel_mode": kernel_support.mode(),
            # live-index identity (serve/delta.py): the segment
            # generation and current delta occupancy — None on a
            # frozen engine, so a stats consumer can tell the worlds
            # apart at a glance
            "generation": getattr(self.engine, "generation", None),
            "segment_rows": getattr(self.engine, "segment_rows", None),
            # overload safety (docs/resilience.md): queue bound, shed /
            # deadline counts, and the ladder's current level+mode —
            # a stats consumer must see a degraded server AS degraded
            "queue_max": (self._admission.queue_max
                          if self._admission else 0),
            "shed": reg.get("serve/shed"),
            "deadline_exceeded": reg.get("serve/deadline_exceeded"),
            "errors": reg.get("serve/errors"),
            "degrade_level": (self._ladder.level if self._ladder else 0),
            "degrade_mode": ("full" if self._mode() is None
                             else str(self._mode())),
            # rolling-window SLO view (docs/observability.md "Windowed
            # SLOs"): p50/p95/p99 + rates from ring DELTAS, None when
            # no window is armed — a stats consumer can tell "no
            # window" from "no traffic"
            "window": (self.window.report()
                       if self.window is not None else None),
        }
