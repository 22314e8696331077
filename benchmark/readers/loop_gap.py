"""Device time outside any step program (before the first, between one
program's end and the next one's start, after the last), per step, on
the least busy device: what the host's step loop leaves the chip idle."""

from benchmark import trace_reduce as tr


def read(red, facts, peaks, spec):
    steps = facts.get("steps_traced", 0)
    if red is None or not red.devices or steps <= 0:
        return None
    dev = tr.least_busy(red)
    if not dev.modules:
        return None
    return tr.between_programs_ns(dev, red.t0, red.t1) / steps * 1e-6
