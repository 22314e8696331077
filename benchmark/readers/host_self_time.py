"""The loop's own host time per step: inside the program's step events
(``step_event``, the ``StepTraceAnnotation`` that ``run_loop`` opens
round each iteration while its tracer is on), the time that no other
event of the same thread covers — ``dispatch`` with its back-pressure
waits, ``metrics_flush`` and whatever the runtime traces inside them
are other events.  Read from the profiler's own host plane, so on the
device's clock (benchmark/scope_reduce.py).  No step event in the
window means nothing to read."""

import os

from benchmark import scope_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(red, facts, peaks, spec):
    steps = facts.get("steps_traced", 0)
    sc = scope_reduce.load(ROOT, red)
    if sc is None or steps <= 0:
        return None
    ns, count = scope_reduce.self_ns(sc, spec["step_event"])
    return ns / steps * 1e-6 if count and ns > 0 else None
