"""Summed device time of the operations whose names match the metric's
``patterns`` (fnmatch), per step, on the least busy device.  Nothing
matched means nothing to read."""

from benchmark import trace_reduce as tr


def op_ns(red, spec) -> float:
    return tr.sum_by_patterns(tr.least_busy(red).ops, spec["patterns"])


def read(red, facts, peaks, spec):
    steps = facts.get("steps_traced", 0)
    if red is None or not red.devices or steps <= 0:
        return None
    ns = op_ns(red, spec)
    return ns / steps * 1e-6 if ns > 0 else None
