"""The aggregation kernels' share of their roofline: the least time the
chip could take for what the kernels have to read, write and compute
(benchmark/costs.py: each operand once, each result once) over the time
their events took.  ``bound`` in the spec's notes says which binds."""

from benchmark import costs
from benchmark import trace_reduce as tr


def read(red, facts, peaks, spec):
    steps = facts.get("steps_traced", 0)
    if red is None or not red.devices or steps <= 0:
        return None
    ns = tr.sum_by_patterns(tr.least_busy(red).ops, spec["patterns"])
    if ns <= 0:
        return None
    cost = costs.aggregate_kernels_step_cost(
        facts["n"], facts["e_block"], facts["e_rest"], facts["widths"],
        facts["msg_bytes"])
    least_s, _ = costs.roofline_seconds(cost, peaks)
    return 100.0 * least_s * steps / (ns * 1e-9)
