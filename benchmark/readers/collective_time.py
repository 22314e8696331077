"""Device time in collective operations per step, mean over the devices:
the union of the intervals of every all-gather, all-to-all,
collective-permute, all-reduce and reduce-scatter event on the device's
operation line (benchmark/mesh_reduce.py; the two events of an
asynchronous pair are each on the line once).  What a transfer overlaps
with compute is not in it.  No collective in the window means nothing to
read (a one-chip program)."""

from benchmark import mesh_reduce


def read(red, facts, peaks, spec):
    steps = facts.get("steps_traced", 0)
    if red is None or not red.devices or steps <= 0:
        return None
    ns = sum(mesh_reduce.exposed_ns(d.ops) for d in red.devices) / len(
        red.devices)
    return ns / steps * 1e-6 if ns > 0 else None
