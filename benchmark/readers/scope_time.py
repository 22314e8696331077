"""Summed device time per step, on the least busy device, of the
operations whose scope path (jax's ``op_name``, benchmark/
scope_reduce.py) matches the metric's ``patterns`` (fnmatch; default
every operation) and none of its ``exclude_paths``; ``exclude_ops`` are
patterns on the operation's name, ``pass`` keeps the backward (``bwd``:
the path holds ``transpose(``) or the forward (``fwd``) alone.  A fusion
counts under its root's path.  A trace without paths, or nothing
matched, means nothing to read."""

import os

from benchmark import scope_reduce
from benchmark import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PASSES = {None: None, "bwd": True, "fwd": False}


def read(red, facts, peaks, spec):
    steps = facts.get("steps_traced", 0)
    sc = scope_reduce.load(ROOT, red)
    if sc is None or not sc.devices or not sc.has_paths or steps <= 0:
        return None
    ns = scope_reduce.path_ns(
        tr.least_busy(sc), spec.get("patterns", ["*"]),
        spec.get("exclude_paths", ()), spec.get("exclude_ops", ()),
        PASSES[spec.get("pass")])
    return ns / steps * 1e-6 if ns > 0 else None
