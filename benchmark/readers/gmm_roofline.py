"""The grouped matmul's share of its roofline: for each event of the
spec's ``calls`` (a kind of call and a regular expression on the
instruction's name), the least time the chip could take for that call
(``costs_moe.gmm_call_cost`` over the rows the step routed to held
experts and the held experts that got any, each the mean a call over
its sparse layers from the driver's facts, and the matrices the call's
result shape names), summed, over the summed
time of those events, on the least busy device.  A recomputed call is a
call.  No such event, or facts without the routed rows, means nothing to
read."""

import re

from benchmark import costs_lm, costs_moe
from benchmark import trace_reduce as tr

FACTS = ("held_rows_mean", "hidden", "moe_width", "held_experts_mean",
         "itemsize")
RESULT = re.compile(r"= \w+\[([\d,]+)\]")


def _matrices(which, dims, hidden, width):
    """(k, n) of the call's matrices from its result's shape."""
    if which == "dw":                  # [experts, k, n]
        return dims[1], dims[2]
    out = dims[-1]
    if which == "fwd":                 # [rows, n]: gate/up or down
        return (hidden, width) if out == width else (width, hidden)
    return (width, hidden) if out == hidden else (hidden, width)


def read(red, facts, peaks, spec):
    if red is None or not red.devices:
        return None
    if any(k not in facts for k in FACTS):
        return None
    rows, hidden, width, experts, itemsize = (facts[k] for k in FACTS)
    least_s = took_ns = 0.0
    for which, pattern in spec["calls"].items():
        rx = re.compile(pattern)
        for s, e, name in tr.least_busy(red).ops:
            shape = RESULT.search(name) if rx.match(name) else None
            if shape is None:
                continue
            k, n = _matrices(which, [int(x) for x in
                                     shape.group(1).split(",")],
                             hidden, width)
            cost = costs_moe.gmm_call_cost(which, rows, k, n, experts,
                                           itemsize)
            least_s += costs_lm.roofline_seconds(cost, peaks)[0]
            took_ns += e - s
    return 100.0 * least_s / (took_ns * 1e-9) if took_ns > 0 else None
