"""Device busy time per step, averaged over the devices used."""

from benchmark import trace_reduce as tr


def read(red, facts, peaks, spec):
    steps = facts.get("steps_traced", 0)
    if red is None or not red.devices or steps <= 0:
        return None
    busy = sum(tr.busy_ns(d) for d in red.devices) / len(red.devices)
    return busy / steps * 1e-6 if busy > 0 else None
