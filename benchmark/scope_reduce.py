"""Scope paths of the device's operations and the program's own spans,
from the same ``.xplane.pb`` that ``trace_reduce`` reads.

``jax.profiler.ProfileData`` shows an event's own stats and nothing of
its *metadata*, which is where the profiler keeps what is the same for
every occurrence of an operation: on the TPU's ``XLA Ops`` line the
event carries ``device_offset_ps`` / ``device_duration_ps`` and the
metadata carries jax's ``op_name`` path (PERF.md section 3 names the
stat).  So this module reads the file itself: the protobuf wire format
of the few ``XSpace`` fields it needs (tsl/profiler/protobuf/
xplane.proto), with the standard library alone, into the same shape of
planes, lines and events that ``trace_reduce.reduce_planes`` takes — an
event's ``stats`` here are its metadata's stats, then its own.

Nothing of ``trace_reduce`` is edited; its constants and helpers are
used as they are (looked up at call time: the CPU tests re-point them).
All times are nanoseconds on the trace's clock.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import functools
import glob
import os
import struct
import types

from benchmark import trace_reduce as tr

# the stat that holds jax's op_name path, by the names the profiler has
# given it (first present wins)
PATH_STATS = ("tf_op", "op_name")
BACKWARD_MARK = "transpose("
HOST_PLANE_PREFIX = "/host:"


# --- the wire format ---------------------------------------------------------


def _varint(buf, i: int) -> tuple:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint field,
    a view of the bytes for a length-delimited or fixed one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield num, val
            continue
        if wire == 2:
            size, i = _varint(buf, i)
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield num, buf[i:i + size]
        i += size


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf, stat_names: dict):
    """(name, value) of one XStat."""
    name, value = None, None
    for num, v in _fields(buf):
        if num == 1:
            name = stat_names.get(v, str(v))
        elif num == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num == 5:
            value = _text(v)
        elif num == 6:
            value = bytes(v)
        elif num == 7:  # a reference to a stat's name used as a string
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf):
    key, value = 0, b""
    for num, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _id_and_name(buf) -> tuple:
    """(id, name) of an XStatMetadata."""
    sid, name = 0, ""
    for num, v in _fields(buf):
        if num == 1:
            sid = v
        elif num == 2:
            name = _text(v)
    return sid, name


def _event_metadata(buf, stat_names: dict) -> tuple:
    """(name, stats) of an XEventMetadata."""
    name, display, stats = "", "", []
    for num, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 4:
            display = _text(v)
        elif num == 5:
            stats.append(_stat(v, stat_names))
    return name or display, stats


def _event(buf, t0_ns: int, metadata: dict, stat_names: dict):
    mid = off_ps = dur_ps = 0
    stats = []
    for num, v in _fields(buf):
        if num == 1:
            mid = v
        elif num == 2:
            off_ps = _signed(v)
        elif num == 3:
            dur_ps = _signed(v)
        elif num == 4:
            stats.append(_stat(v, stat_names))
    name, meta_stats = metadata.get(mid, ("", []))
    return types.SimpleNamespace(
        name=name, start_ns=t0_ns + off_ps * 1e-3,
        duration_ns=dur_ps * 1e-3, stats=meta_stats + stats)


def _line(buf, metadata: dict, stat_names: dict):
    name, t0_ns, events = "", 0, []
    for num, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            t0_ns = _signed(v)
        elif num == 4:
            events.append(v)
    return types.SimpleNamespace(name=name, events=[
        _event(e, t0_ns, metadata, stat_names) for e in events])


def _plane(buf):
    name, lines, event_meta, stat_meta = "", [], [], []
    for num, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            event_meta.append(v)
        elif num == 5:
            stat_meta.append(v)
    # both maps before any line: an event names its metadata by id
    stat_names = dict(_id_and_name(_map_entry(e)[1]) for e in stat_meta)
    metadata = {key: _event_metadata(meta, stat_names)
                for key, meta in map(_map_entry, event_meta)}
    return types.SimpleNamespace(name=name, lines=[
        _line(ln, metadata, stat_names) for ln in lines])


def read_xplane(path: str) -> list:
    """The file's planes as objects with ``name`` and ``lines``; a line
    has ``name`` and ``events``; an event ``name``, ``start_ns``,
    ``duration_ns`` and ``stats`` = [(name, value)], the metadata's
    first."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(v) for num, v in _fields(buf) if num == 1]


# --- from planes to scoped operations and host spans ------------------------


@dataclasses.dataclass
class ScopedDevice:
    name: str
    ops: list        # (start_ns, end_ns, name, path), clipped to the window


@dataclasses.dataclass
class Scoped:
    t0: float
    t1: float
    devices: list     # [ScopedDevice]
    has_paths: bool   # some operation in the window carried the stat
    host: dict        # {(plane, line): [(start_ns, end_ns, name)]}, sorted


def _path(stats) -> str:
    found = dict(stats) if stats else {}
    for key in PATH_STATS:
        if found.get(key):
            # "<op_name>:<op type>", the type empty for jax's programs
            path, _, _ = str(found[key]).rpartition(":")
            return path or str(found[key])
    return ""


def scope_planes(planes) -> Scoped:
    """``planes`` as ``trace_reduce.reduce_planes`` takes them; an event
    may also have ``stats``, an iterable of (name, value).  Device
    operations keep their scope path ("" where the trace gives none);
    the host planes' other events inside the window are kept by line,
    for the program's spans."""
    begin = end = None
    dev_raw, host_raw = [], {}
    for plane in planes:
        is_dev = plane.name.startswith(tr.DEVICE_PLANE_PREFIX)
        is_host = plane.name.startswith(HOST_PLANE_PREFIX)
        ops = []
        for line in plane.lines:
            is_ops = is_dev and line.name.startswith(tr.OP_LINE)
            for ev in line.events:
                s = float(ev.start_ns)
                if ev.name == tr.WINDOW_BEGIN:
                    begin = s
                elif ev.name == tr.WINDOW_END:
                    end = s
                elif is_ops:
                    ops.append((s, s + float(ev.duration_ns), ev.name,
                                _path(getattr(ev, "stats", None))))
                elif is_host:
                    host_raw.setdefault((plane.name, line.name), []).append(
                        (s, s + float(ev.duration_ns), ev.name))
        if is_dev:
            dev_raw.append((plane.name, ops))
    if begin is None or end is None or end <= begin:
        raise ValueError("the trace lacks the window's two annotations")
    devices = []
    for name, ops in sorted(dev_raw):
        clipped = sorted((max(s, begin), min(e, end), n, p)
                         for s, e, n, p in ops if min(e, end) > max(s, begin))
        devices.append(ScopedDevice(name, clipped))
    host = {key: sorted(ev for ev in evs if begin <= ev[0] and ev[1] <= end)
            for key, evs in host_raw.items()}
    return Scoped(begin, end, devices,
                  any(p for d in devices for _, _, _, p in d.ops),
                  {k: v for k, v in host.items() if v})


def newest_xplane(root: str):
    """The newest trace under the checkout's cache root (where the
    drivers write theirs), or None."""
    files = glob.glob(os.path.join(root, ".cache", "benchmark", "trace",
                                   "**", "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


@functools.lru_cache(maxsize=2)
def _scope_file(path: str, mtime: float) -> Scoped:
    return scope_planes(read_xplane(path))


def load(root: str, red):
    """The trace that ``red`` was reduced from, reduced once a process
    however many metrics ask; None where the newest trace under ``root``
    is not that one (another run's: its window differs) or none is
    there."""
    path = newest_xplane(root) if red is not None else None
    if path is None:
        return None
    try:
        sc = _scope_file(path, os.path.getmtime(path))
    except ValueError:
        return None
    same = abs(sc.t0 - red.t0) < 1e3 and abs(sc.t1 - red.t1) < 1e3
    return sc if same else None


def _any(text: str, patterns) -> bool:
    return any(fnmatch.fnmatchcase(text, p) for p in patterns)


def path_ns(dev: ScopedDevice, patterns=("*",), exclude_paths=(),
            exclude_ops=(), backward=None) -> float:
    """Summed duration of the device's operations whose path matches a
    pattern and none of ``exclude_paths``, whose name matches none of
    ``exclude_ops`` and, with ``backward`` given, whose path holds the
    backward's mark or does not."""
    total = 0.0
    for s, e, name, path in dev.ops:
        if not _any(path, patterns) or _any(path, exclude_paths):
            continue
        if exclude_ops and _any(name, exclude_ops):
            continue
        if backward is not None and (BACKWARD_MARK in path) != backward:
            continue
        total += e - s
    return total


def self_ns(sc: Scoped, step_event: str) -> tuple:
    """(summed self time, count) of the ``step_event`` host events: the
    time inside each that no other event of its thread's line, lying
    inside it, covers."""
    total, count = 0.0, 0
    for events in sc.host.values():
        steps = [ev for ev in events if ev[2] == step_event]
        for s, e, _ in steps:
            inside = [(max(cs, s), min(ce, e)) for cs, ce, n in events
                      if n != step_event and cs >= s and ce <= e]
            total += (e - s) - tr.union_ns(inside)
            count += 1
    return total, count
