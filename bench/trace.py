"""Reduce a profiler trace to what the per-layer metrics read.

`load(path)` reads an `.xplane.pb` into flat events.  `reduce(events)`
returns, over the traced window (the host span `bench.traced`):

    window       (start, end) of the traced window, seconds
    devices      how many devices ran an op inside the window (all the
                 trace's devices where none did)
    busy_s       device busy time: the union of a device's leaf op
                 intervals inside the window, averaged over those devices
                 (a control-flow op such as the scan's `while` encloses the
                 ops it runs, and would hide the gaps between them)
    busy_sum_s   the same union summed over those devices: device-seconds
    gaps         idle intervals (start, end) of the first of those devices
                 inside the window
    spans        the `bench.*` host spans, which `breakdown` labels the
                 longest gaps with
    module_gaps  time between consecutive runs of the main program (the one
                 with the most device time) on that first device: one
                 window's end to the next one's start, seconds
    op_totals    device seconds per leaf op, summed over the devices, so
                 that they add up to `busy_sum_s` where ops do not
                 overlap; an op is named by its HLO instruction
                 (`%fusion.12`)

A device plane is one named `/device:<KIND>:<n>`; its ops are the events of
the line `XLA Ops`, its program executions those of `XLA Modules`.  Host
spans are events whose name starts with `bench.` on any `/host:` plane.
Times are seconds on the trace's common clock.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
TRACED = "bench.traced"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: float      # seconds
    end: float


def find(trace_dir: str) -> str:
    """The newest `.xplane.pb` the profiler wrote under `trace_dir`."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> list:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if host and not ev.name.startswith(SPAN_PREFIX):
                    continue
                s = ev.start_ns * 1e-9
                out.append(Event(plane.name, line.name,
                                 ev.name.split(" = ", 1)[0], s,
                                 s + ev.duration_ns * 1e-9))
    return out


def leaves(events) -> list:
    """The events that enclose no other event of theirs."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    outer, stack = set(), []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= order[stack[-1]].end:
            outer.add(stack[-1])
        stack.append(i)
    return [e for i, e in enumerate(order) if i not in outer]


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(x) for x in merged]


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _label(spans, lo: float, hi: float) -> str:
    """The innermost host span that overlaps [lo, hi] the most."""
    over = [sp for sp in spans if sp.start < hi and sp.end > lo]
    inner = [sp for sp in over if not any(
        o is not sp and sp.start <= o.start and o.end <= sp.end
        for o in over)]
    if not inner:
        return "no bench span"
    return max(inner, key=lambda sp: min(sp.end, hi) - max(sp.start, lo)).name


def reduce(events: list) -> dict:
    spans = [e for e in events if e.plane.startswith("/host:")]
    traced = [e for e in spans if e.name == TRACED]
    if not traced:
        raise ValueError(f"the trace has no {TRACED} span")
    lo = min(e.start for e in traced)
    hi = max(e.end for e in traced)
    devices = sorted({e.plane for e in events
                      if DEVICE_PLANE.match(e.plane)},
                     key=lambda p: int(p.rsplit(":", 1)[1]))
    if not devices:
        raise ValueError("the trace has no device plane")
    busy, totals = {}, defaultdict(float)
    for dev in devices:
        ops = leaves([e for e in events
                      if e.plane == dev and e.line == OPS_LINE])
        busy[dev] = union(_clip([(e.start, e.end) for e in ops], lo, hi))
        for e in ops:
            s, t = max(e.start, lo), min(e.end, hi)
            if t > s:
                totals[e.name] += t - s
    used = [d for d in devices if busy[d]] or devices
    busy_sum = sum(sum(e - s for s, e in busy[d]) for d in used)
    first = busy[used[0]]
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    mods = [e for e in events if e.plane == used[0]
            and e.line == MODULES_LINE and e.end > lo and e.start < hi]
    per = defaultdict(float)
    for e in mods:
        per[e.name] += e.end - e.start
    main = sorted((e.start, e.end) for e in mods
                  if per and e.name == max(per, key=per.get))
    module_gaps = [max(b[0] - a[1], 0.0) for a, b in zip(main, main[1:])]
    return {
        "window": (lo, hi),
        "devices": len(used),
        "busy_s": busy_sum / len(used),
        "busy_sum_s": busy_sum,
        "gaps": gaps,
        "spans": spans,
        "modules": len(main),
        "module_gaps": module_gaps,
        "op_totals": dict(totals),
    }


def breakdown(red: dict, top: int = 10) -> dict:
    """The device ops that took the most time and the longest idle gaps,
    each gap labelled by what the host was doing: of the innermost
    `bench.*` spans open in it, the one that covers most of it."""
    ops = sorted(red["op_totals"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["gaps"], key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[_label(red["spans"], s, e), e - s]
                          for s, e in gaps]}
