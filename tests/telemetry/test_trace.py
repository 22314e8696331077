"""Trace spans (telemetry/trace.py): nesting, disabled-mode zero cost,
boundary flush vs cumulative totals, Chrome dump shape."""

import json
import time

from hyperspace_tpu.telemetry import trace


def _fresh(**kw):
    return trace.Tracer(enabled=True, **kw)


def test_disabled_span_is_shared_nullcontext():
    # the zero-cost contract: disabled (the default) the module-level
    # span() returns ONE shared stateless context manager — no
    # allocation, no recording
    t = trace.default_tracer()
    was = t.enabled
    t.enabled = False
    try:
        before = t.total_fields()
        a = trace.span("x")
        b = trace.span("y")
        assert a is b is trace._NULL
        with a:
            pass
        assert t.total_fields() == before  # nothing recorded
    finally:
        t.enabled = was


def test_span_nesting_records_both_levels():
    t = _fresh(keep_events=True)
    with t.span("outer"):
        with t.span("inner"):
            time.sleep(0.01)
    fields = t.total_fields()
    assert fields["span/outer_n"] == 1 and fields["span/inner_n"] == 1
    # containment: the outer span covers the inner one
    assert fields["span/outer_s"] >= fields["span/inner_s"] > 0
    (n1, t1a, t1b, _, _), (n2, t2a, t2b, _, _) = sorted(
        t._events, key=lambda e: e[1])
    assert (n1, n2) == ("outer", "inner")
    assert t1a <= t2a and t2b <= t1b


def test_flush_fields_resets_boundary_but_not_totals():
    t = _fresh()
    with t.span("a"):
        pass
    first = t.flush_fields()
    assert "span/a_s" in first
    assert t.flush_fields() == {}  # boundary aggregate drained
    with t.span("a"):
        pass
    assert "span/a_s" in t.flush_fields()
    assert t.total_fields()["span/a_n"] == 2  # cumulative survives


def test_chrome_dump_is_perfetto_loadable_shape(tmp_path):
    t = _fresh(keep_events=True)
    with t.span("dispatch"):
        with t.span("metrics_flush"):
            pass
    path = str(tmp_path / "trace.json")
    n = t.dump_chrome_trace(path)
    assert n == 2
    doc = json.loads(open(path).read())
    assert isinstance(doc["traceEvents"], list)
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X"
        assert set(ev) >= {"name", "pid", "tid", "ts", "dur"}
        assert ev["dur"] >= 0
    # dump DRAINS: a second run's dump starts from a clean timeline
    assert t.dump_chrome_trace(str(tmp_path / "t2.json")) == 0


def test_span_args_land_in_chrome_dump_and_late_fills_count(tmp_path):
    """The optional args dict rides into the trace event; it is held by
    REFERENCE so a call site can fill in late-known metadata (cache
    hits) before the span exits.  Spans without args stay bare."""
    t = _fresh(keep_events=True)
    meta = {"batch": 32}
    with t.span("query", args=meta):
        meta["cache_hits"] = 7  # filled in mid-span, batcher-style
    with t.span("plain"):
        pass
    t.record_span("ckpt_save", 1.0, 2.0, args={"step": 64})
    path = str(tmp_path / "trace.json")
    assert t.dump_chrome_trace(path) == 3
    evs = {e["name"]: e for e in json.loads(open(path).read())["traceEvents"]}
    assert evs["query"]["args"] == {"batch": 32, "cache_hits": 7}
    assert evs["ckpt_save"]["args"] == {"step": 64}
    assert "args" not in evs["plain"]


def test_keep_events_off_aggregates_without_retaining():
    t = _fresh(keep_events=False)
    for _ in range(10):
        with t.span("s"):
            pass
    assert len(t._events) == 0
    assert t.total_fields()["span/s_n"] == 10


def test_retention_ring_keeps_the_newest_events(monkeypatch):
    # the dump's crash-diagnosis job needs the timeline's TAIL: at the
    # cap, the OLDEST events evict (ring), and the drop count is honest
    import collections

    t = _fresh(keep_events=True)
    t._events = collections.deque(maxlen=3)
    for i in range(5):
        with t.span(f"s{i}"):
            pass
    assert [e[0] for e in t._events] == ["s2", "s3", "s4"]
    assert t._dropped == 2


def test_enable_disable_roundtrip():
    t = trace.default_tracer()
    was_enabled, was_keep = t.enabled, t.keep_events
    try:
        got = trace.enable(keep_events=True)
        assert got is t and t.enabled and t.keep_events
        with trace.span("roundtrip"):
            pass
        assert t.total_fields().get("span/roundtrip_n") == 1
        trace.disable()
        assert not t.enabled
    finally:
        t.enabled, t.keep_events = was_enabled, was_keep


def test_disabled_step_marker_is_the_same_nullcontext():
    # the loop's per-iteration step marker keeps the zero-cost contract:
    # no _Span, no profiler annotation, nothing recorded
    t = trace.default_tracer()
    was = t.enabled
    t.enabled = False
    try:
        before = t.total_fields()
        assert trace.span("train_step", None, step_num=7) is trace._NULL
        assert t.span("train_step", step_num=7) is trace._NULL
        assert t.total_fields() == before
    finally:
        t.enabled = was


def test_enabled_span_enters_a_profiler_annotation(monkeypatch):
    # what a jax.profiler session sees of an enabled span: a step marker
    # named without the "_step" suffix, else the span's own name + args
    import jax.profiler as jp

    made = []

    class Ann:
        def __init__(self, name, **kw):
            made.append((type(self).__name__, name, kw, []))
            self._log = made[-1][3]

        def __enter__(self):
            self._log.append("enter")

        def __exit__(self, *exc):
            self._log.append("exit")

    monkeypatch.setattr(jp, "TraceAnnotation", type("Plain", (Ann,), {}))
    monkeypatch.setattr(jp, "StepTraceAnnotation", type("Step", (Ann,), {}))
    t = _fresh()
    with t.span("train_step", {"step": 3, "chunk": 1}, step_num=3):
        with t.span("dispatch", {"step": 3}):
            pass
        with t.span("metrics_flush"):
            pass
    assert made == [
        ("Step", "train", {"step_num": 3}, ["enter", "exit"]),
        ("Plain", "dispatch", {"step": 3}, ["enter", "exit"]),
        ("Plain", "metrics_flush", {}, ["enter", "exit"])]
    assert t.total_fields()["span/train_step_n"] == 1
