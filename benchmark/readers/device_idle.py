"""Share of the traced window in which no operation ran, on the least
busy device: 1 - (union of its operation intervals / window)."""

from benchmark import trace_reduce as tr


def read(red, facts, peaks, spec):
    if red is None or not red.devices or red.window_ns <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_ns(tr.least_busy(red)) / red.window_ns)
