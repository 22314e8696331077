"""Collective operations in a reduced trace (``trace_reduce.Reduced`` or
``scope_reduce.Scoped``: a device's ``ops`` are tuples that begin with
start, end, name).

A device line names an operation by its HLO text.  A collective is told
by its opcode: ``all-gather``, ``all-to-all``, ``collective-permute``,
``all-reduce``, ``reduce-scatter``, each also as an asynchronous pair
``<kind>-start`` / ``<kind>-done`` whose ``done`` names its ``start`` as
operand.  Two readings of one device's collectives:

- :func:`exposed_ns`: the union of the operations' own intervals, the
  time the device's line is held by collectives (a pair's two events are
  each on the line once, and nothing is counted twice);
- :func:`in_flight_ns`: the union of the intervals from each ``start``'s
  beginning to its ``done``'s end (a synchronous collective's own
  interval), the time a transfer may have been in progress; never less
  than the first, so a share of a peak taken over it can only read low.
"""

from __future__ import annotations

import re

from benchmark import trace_reduce as tr

KINDS = ("all-gather", "all-to-all", "collective-permute", "all-reduce",
         "reduce-scatter")
_OPCODE = re.compile(r"^(%[\w.\-]+) = .*? ([a-z][\w\-]*)\(")
_START_OPERAND = re.compile(r"\(.*?(%[\w.\-]+)")


def collective_kind(name: str):
    """(kind, phase, instruction) of a collective's event, phase one of
    ``sync``, ``start``, ``done``; None for any other operation."""
    m = _OPCODE.match(name)
    if not m:
        return None
    inst, opcode = m.group(1), m.group(2)
    for kind in KINDS:
        if opcode == kind:
            return kind, "sync", inst
        if opcode in (kind + "-start", kind + "-done"):
            return kind, opcode[len(kind) + 1:], inst
    return None


def collective_ops(ops) -> list:
    """The device's collective events, (start, end, name) each."""
    return [(op[0], op[1], op[2]) for op in ops if collective_kind(op[2])]


def pairs(ops) -> list:
    """(start_ns, end_ns, kind) of every collective: a synchronous one's
    own interval; of a pair, from its ``start``'s beginning to its
    ``done``'s end.  A ``done`` whose ``start`` lies outside the window
    counts from its own beginning, a ``start`` without ``done`` to its
    own end."""
    out, open_starts = [], {}
    for s, e, name in sorted(collective_ops(ops)):
        kind, phase, inst = collective_kind(name)
        if phase == "sync":
            out.append((s, e, kind))
        elif phase == "start":
            open_starts[inst] = (s, e, kind)
        else:
            body = name.split(" = ", 1)[1]
            m = _START_OPERAND.search(body[body.index(kind + "-done"):])
            began = open_starts.pop(m.group(1), None) if m else None
            out.append((began[0] if began else s, e, kind))
    out.extend(open_starts.values())
    return sorted(out)


def exposed_ns(ops) -> float:
    return tr.union_ns(collective_ops(ops))


def in_flight_ns(ops) -> float:
    return tr.union_ns(pairs(ops))
