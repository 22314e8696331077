"""The spread over the devices of the time each spends outside
collectives, per step: the device with the most work of its own less the
one with the least.  What an uneven partition (edges a shard, rows a
shard has to gather) costs: the device that finishes its part first
waits for the others inside a collective, which its line counts as
busy, so the spread of busy time reads nothing and the spread of the
time outside collectives reads the wait.  One device, or a program
without a collective, means nothing to read."""

from benchmark import mesh_reduce
from benchmark import trace_reduce as tr


def read(red, facts, peaks, spec):
    steps = facts.get("steps_traced", 0)
    if red is None or len(red.devices) < 2 or steps <= 0:
        return None
    if not any(mesh_reduce.collective_ops(d.ops) for d in red.devices):
        return None
    own = [tr.union_ns([op for op in d.ops
                        if mesh_reduce.collective_kind(op[2]) is None])
           for d in red.devices]
    return (max(own) - min(own)) / steps * 1e-6
