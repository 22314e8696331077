"""The least reduction from a profiler trace to numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` wrote with nothing but
``jax.profiler.ProfileData``: picks the device planes, takes the
intervals of their operation line and of their program ("module") line,
clips them to the traced window, and puts the program's own host spans
(a Chrome ``trace_events`` file on the ``perf_counter`` clock) onto the
trace's clock through one anchor annotation.

All times inside are nanoseconds on the trace's clock; readers turn them
into the units of their metrics.  Nothing here knows a model.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import glob
import json
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
WINDOW_BEGIN = "bench_window_begin"
WINDOW_END = "bench_window_end"


@dataclasses.dataclass
class Device:
    name: str
    ops: list        # (start_ns, end_ns, name), clipped to the window
    modules: list    # (start_ns, end_ns, name), clipped to the window


@dataclasses.dataclass
class Reduced:
    t0: float                 # window start, trace clock, ns
    t1: float                 # window end
    devices: list             # [Device]
    host_spans: list          # (start_ns, end_ns, name) on the trace clock

    @property
    def window_ns(self) -> float:
        return self.t1 - self.t0


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _clip(events, t0, t1):
    out = []
    for s, e, name in events:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            out.append((s, e, name))
    out.sort()
    return out


def union_ns(intervals) -> float:
    """Length of the union of (start, end, ...) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for iv in sorted(intervals):
        s, e = iv[0], iv[1]
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, t0: float, t1: float) -> list:
    """The idle stretches of [t0, t1] that no interval covers."""
    out, edge = [], t0
    for iv in sorted(intervals):
        s, e = iv[0], iv[1]
        if s > edge:
            out.append((edge, min(s, t1)))
        edge = max(edge, e)
        if edge >= t1:
            break
    if edge < t1:
        out.append((edge, t1))
    return out


def sum_by_patterns(events, patterns) -> float:
    """Summed duration of the events whose name matches any pattern
    (``fnmatch``, case-sensitive)."""
    return sum(e - s for s, e, name in events
               if any(fnmatch.fnmatchcase(name, p) for p in patterns))


_HLO = re.compile(r"^(%[\w.\-]+) = (\(?[a-z0-9]+\[[0-9,]*\])\S*.*? "
                  r"([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """A device line names an operation by its whole HLO text; keep the
    instruction, its first result shape, its opcode and, for a custom
    call, its target: ``%conv0.9 f32[169472,256] custom-call
    tpu_custom_call``."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    out = f"{m.group(1)} {m.group(2).lstrip('(')} {m.group(3)}"
    return out + (f" {target.group(1)}" if target else "")


def totals_by_name(events) -> dict:
    out: dict = {}
    for s, e, name in events:
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def busy_ns(dev: Device) -> float:
    return union_ns(dev.ops)


def least_busy(red: Reduced) -> Device:
    return min(red.devices, key=busy_ns)


def between_programs_ns(dev: Device, t0: float, t1: float) -> float:
    """Device idle time outside any program: before the first, between
    one program's end and the next one's start, after the last."""
    return sum(e - s for s, e in gaps(dev.modules, t0, t1))


def label_gaps(gap_list, host_spans, top: int = 10) -> list:
    """The ``top`` longest gaps as [label, seconds]; the label is the
    innermost host span that covers the gap's midpoint, else "other"."""
    out = []
    for s, e in sorted(gap_list, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        cover = [(he - hs, name) for hs, he, name in host_spans
                 if hs <= mid <= he]
        out.append([min(cover)[1] if cover else "other", (e - s) * 1e-9])
    return out


def read_host_spans(chrome_path: str, anchor_perf_s: float,
                    anchor_trace_ns: float) -> list:
    """Host spans from a Chrome trace_events file whose ``ts`` are
    ``perf_counter`` microseconds, moved onto the trace clock."""
    if not chrome_path or not os.path.exists(chrome_path):
        return []
    with open(chrome_path) as f:
        doc = json.load(f)
    shift = anchor_trace_ns - anchor_perf_s * 1e9
    return [(ev["ts"] * 1e3 + shift, (ev["ts"] + ev["dur"]) * 1e3 + shift,
             ev["name"]) for ev in doc.get("traceEvents", [])
            if ev.get("ph") == "X"]


def reduce_planes(planes, *, anchor_perf_s=None,
                  chrome_path=None) -> Reduced:
    """``planes``: an iterable of objects with ``name`` and ``lines``;
    each line has ``name`` and ``events``; each event ``name``,
    ``start_ns`` and ``duration_ns`` (``ProfileData.planes`` or a
    hand-built stand-in).  Device planes and their lines are told by
    name prefix (the module's constants)."""
    begin = end = None
    raw = []
    for plane in planes:
        is_dev = plane.name.startswith(DEVICE_PLANE_PREFIX)
        ops, mods = [], []
        for line in plane.lines:
            into = None
            if is_dev and line.name.startswith(OP_LINE):
                into = ops
            elif is_dev and line.name.startswith(MODULE_LINE):
                into = mods
            for ev in line.events:
                s = float(ev.start_ns)
                if ev.name == WINDOW_BEGIN:
                    begin = s
                elif ev.name == WINDOW_END:
                    end = s
                elif into is not None:
                    into.append((s, s + float(ev.duration_ns), ev.name))
        if is_dev:
            raw.append((plane.name, ops, mods))
    if begin is None or end is None or end <= begin:
        raise ValueError("the trace lacks the window's two annotations")
    devices = [Device(name, _clip(ops, begin, end), _clip(mods, begin, end))
               for name, ops, mods in sorted(raw)]
    spans = []
    if anchor_perf_s is not None:
        spans = _clip(read_host_spans(chrome_path, anchor_perf_s, begin),
                      begin, end)
    return Reduced(begin, end, devices, spans)


def reduce_file(xplane_path: str, **kw) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(xplane_path).planes, **kw)


def breakdown(red: Reduced, top: int = 10) -> dict:
    dev = least_busy(red)
    by_name = sorted(totals_by_name(dev.ops).items(),
                     key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[short_name(n), ns * 1e-9] for n, ns in by_name],
        "idle_gaps": label_gaps(gaps(dev.ops, red.t0, red.t1),
                                red.host_spans, top),
    }
