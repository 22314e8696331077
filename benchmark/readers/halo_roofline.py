"""The exchange's share of the interconnect's peak: the least time the
chip's links could take for the rows the busiest shard needs from the
others (benchmark/costs_mesh.py: each row once, both passes, every
layer) over the time the exchange had, on the least busy device: the
union of the intervals of the operations under the scope
``halo_exchange`` and, for an asynchronous collective among them, of the
whole stretch from its start to its done (benchmark/mesh_reduce.py), so
a transfer hidden behind compute is never timed shorter than it ran."""

import os

from benchmark import costs_mesh, mesh_reduce, scope_reduce
from benchmark import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(red, facts, peaks, spec):
    steps = facts.get("steps_traced", 0)
    rows = facts.get(spec["gauge"])
    sc = scope_reduce.load(ROOT, red)
    if (sc is None or not sc.devices or not sc.has_paths or steps <= 0
            or not rows):
        return None
    dev = tr.least_busy(sc)
    ops = [op for op in dev.ops
           if scope_reduce._any(op[3], spec["patterns"])]
    ns = tr.union_ns([(s, e) for s, e, _, _ in ops]
                     + mesh_reduce.pairs(ops))
    if ns <= 0:
        return None
    least_s = costs_mesh.halo_seconds(rows, facts["widths"],
                                      facts["msg_bytes"], peaks)
    return 100.0 * least_s * steps / (ns * 1e-9)
