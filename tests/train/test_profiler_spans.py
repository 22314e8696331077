"""The program's spans inside the profiler's own trace: with the tracer
on, a ``jax.profiler`` session round ``run_loop`` holds one ``train``
step event per loop iteration (``step_num`` = the step at dispatch) with
``dispatch`` inside it, on the host plane; with it off, none."""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from hyperspace_tpu.cli.train import RunConfig
from hyperspace_tpu.telemetry import trace
from hyperspace_tpu.train import loop
from hyperspace_tpu.train.telemetry import StepPhases

pytestmark = pytest.mark.filterwarnings(
    "ignore:builtin type:DeprecationWarning")


@pytest.fixture(autouse=True)
def _tracer_as_found():
    t = trace.default_tracer()
    was = (t.enabled, t.keep_events)
    t.reset()
    yield
    t.reset()
    t.enabled, t.keep_events = was


def _host_events(trace_dir):
    """[(line name, event name, start, end, stats)] of the host planes."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(line.name, ev.name, ev.start_ns,
                     ev.start_ns + ev.duration_ns, dict(ev.stats))
                    for ev in line.events]
    return out


def _profiled_loop(tmp_path, steps, **run_kw):
    step = jax.jit(lambda s: (s + 1.0, jnp.sum(s)))
    state = jnp.zeros((8,))
    step(state)  # compiled before the session
    d = str(tmp_path / "prof")
    jax.profiler.start_trace(d)
    try:
        loop.run_loop(RunConfig(steps=steps, eval_every=2, **run_kw), state,
                      step)
    finally:
        jax.profiler.stop_trace()
    return _host_events(d)


def test_step_markers_and_dispatch_inside_them(tmp_path):
    events = _profiled_loop(tmp_path, 5, telemetry=True)
    steps = sorted((e for e in events if e[1] == "train"),
                   key=lambda e: e[2])
    assert [e[4]["step_num"] for e in steps] == [0, 1, 2, 3, 4]
    for line, _, s, e, _ in steps:
        inside = [n for ln, n, cs, ce, _ in events
                  if ln == line and s <= cs and ce <= e and n != "train"]
        assert "dispatch" in inside
    # dispatch carries the span's args; the flush sits in its step too
    disp = [e for e in events if e[1] == "dispatch"]
    assert sorted(e[4]["step"] for e in disp) == [0, 1, 2, 3, 4]
    assert all(e[4]["chunk"] == 1 for e in disp)
    flushes = [e for e in events if e[1] == "metrics_flush"]
    assert len(flushes) == 2
    assert all(any(s[2] <= f[2] and f[3] <= s[3] for s in steps)
               for f in flushes)


def test_tracer_off_leaves_the_profile_without_spans(tmp_path):
    events = _profiled_loop(tmp_path, 3)
    assert not [e for e in events
                if e[1] in ("train", "dispatch", "metrics_flush")]
    assert not trace.default_tracer().enabled


def test_step_phases_are_spans(tmp_path):
    # StepPhases has no annotation route of its own: a phase is a span,
    # so it shows wherever spans show
    t = trace.enable(keep_events=True)
    ph = StepPhases()
    with ph.phase("host_gather"):
        pass
    assert [e[0] for e in t._events] == ["host_gather"]
    assert "host_gather" in ph.last
    trace.disable()
    with ph.phase("write_back"):
        pass
    assert [e[0] for e in t._events] == ["host_gather"]
    assert not hasattr(ph, "annotate")
