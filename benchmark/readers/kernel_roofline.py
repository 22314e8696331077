"""The flash kernel's dot form's share of its roofline: for each of the
spec's ``calls`` (a kind of call and a regular expression on the
instruction's name, which leads the operation's text on the device
line), the number of its events in the window times the least time the
chip could take for one such call (``costs_lm.flash_dot_call_cost`` on
the driver's facts: each operand once, each result once, what the call
has to compute), over the summed time of those events, on the least
busy device.  A recomputed
call is a call: it needs its operations again, so it counts on both
sides (what recomputation costs the step is ``step_mfu_pct``'s to
show).  No such event in the window, or facts without the shapes, means
nothing to read."""

import re

from benchmark import costs_lm
from benchmark import trace_reduce as tr

SHAPES = ("heads", "seq", "head_dim", "itemsize")


def read(red, facts, peaks, spec):
    if red is None or not red.devices:
        return None
    if any(k not in facts for k in SHAPES):
        return None
    shapes = [facts[k] for k in SHAPES]
    ops = tr.least_busy(red).ops
    least_s = took_ns = 0.0
    for which, pattern in spec["calls"].items():
        rx = re.compile(pattern)
        spans = [e - s for s, e, name in ops if rx.match(name)]
        if spans:
            cost = costs_lm.flash_dot_call_cost(which, *shapes)
            least_s += len(spans) * costs_lm.roofline_seconds(cost, peaks)[0]
            took_ns += sum(spans)
    return 100.0 * least_s / (took_ns * 1e-9) if took_ns > 0 else None
