"""The windowed flash calls' share of their roofline: for each of the
spec's ``calls`` (a kind of call and a regular expression on the
instruction's name, which leads the operation's text on the device
line), the number of its events in the window times the least time the
chip could take for one such call (``costs_moe.window_call_cost`` on the
driver's facts: only keys inside the window count, each operand once,
each result once), over the summed time of those events, on the least
busy device.  A recomputed call is a call.  No such event, or facts
without the shapes, means nothing to read."""

import re

from benchmark import costs_lm, costs_moe
from benchmark import trace_reduce as tr

SHAPES = ("sliding_heads", "kv_heads", "seq", "head_dim", "window",
          "itemsize")


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
            cost = costs_moe.window_call_cost(which, *shapes)
            least_s += len(spans) * costs_lm.roofline_seconds(cost, peaks)[0]
            took_ns += sum(spans)
    return 100.0 * least_s / (took_ns * 1e-9) if took_ns > 0 else None
