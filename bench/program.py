"""What the program under test says about itself: the cycle's phase
scopes and its host spans.

The program (`repro.core.spans`) traces every op of a simulated cycle
under a `cycle.<phase>` named scope, which XLA keeps in each HLO
instruction's `op_name` metadata, and times its set-up in `repro.*` host
spans.  The readers in `bench/metrics/` take from here:

    window_ops()        {op name: phase or None} of the window executable
                        the program compiled in this run, from its
                        optimized HLO text; None when the program names
                        no phase
    ms_per_lane_cycle   one phase's device time, summed over the devices,
                        over the traced lane-cycles, from the trace's
                        per-op totals
    span_totals()       the program's host-span seconds by name; None
                        when the program has no spans

A fused op counts under one phase: its own `op_name`, or where XLA left
it without one, its fused computation's.  An op of the window executable under no phase
is `unscoped`: the key chain and the loop and cond scaffolding.  Ops of
other programs are not in the map, and are not attributed; the per-op
totals name ops without their program, so an op of another program that
shares a name with one of the window executable's would be (the harness's
own programs are a few scalar ops per window).

    python3 bench/program.py <trace.xplane.pb> <window.hlo.txt>

prints, for a profile and the executable's HLO text kept from a run, the
device seconds per phase of the ops inside the main program's runs, the
unscoped ops, and the longest idle gaps inside those runs, each labelled
`<phase before> -> <phase after>`.
"""
from __future__ import annotations

import json
import re
import sys
from collections import Counter, defaultdict

UNSCOPED = "unscoped"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z_][\w.\-]*)\s=\s")
_OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="((?:[^"\\]|\\.)*)"')
_PHASE = re.compile(r"(?:^|[/(])cycle\.([a-z]+)(?=[/)]|$)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([A-Za-z_][\w.\-]*)\s*\(")
_CALLS = re.compile(r"\bcalls=%?([A-Za-z_][\w.\-]*)")
_MAPS: dict = {}


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def op_phases(hlo_text: str) -> dict:
    """{instruction name: innermost `cycle.<phase>` of its op_name, or
    None} over every instruction of an HLO module's text.  A fusion
    whose own metadata names no phase (the TPU compiler leaves some of
    its scatter fusions bare) takes the phase of its fused computation's
    root, else the most common phase among that computation's ops."""
    own, calls, body, root = {}, {}, defaultdict(list), {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            head = _COMPUTATION.match(line)
            if head:
                comp = head.group(1)
            continue
        name = m.group(1)
        body[comp].append(name)
        if line.lstrip().startswith("ROOT "):
            root[comp] = name
        op = _OP_NAME.search(line)
        found = _PHASE.findall(op.group(1)) if op else []
        own[name] = found[-1] if found else None
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
    done = {}

    def phase(name):
        if own[name] or name not in calls:
            return own[name]
        if name not in done:
            done[name] = None                # a cycle reads as no phase
            ops = body.get(calls[name], [])
            tally = Counter(p for p in map(phase, ops) if p)
            top = root.get(calls[name])
            done[name] = (top and phase(top)) or (
                tally.most_common(1)[0][0] if tally else None)
        return done[name]

    return {name: phase(name) for name in own}


def _cache_dir() -> str:
    import jax
    return jax.config.jax_compilation_cache_dir or "none"


def window_ops():
    """The op->phase map of the newest window executable the program
    compiled or loaded in this process; None when the program has none,
    and, with a note on standard error, when its HLO names no phase."""
    try:
        from repro.core.engine.sweep import window_executables
    except ImportError:         # a program from before the phase scopes
        return None
    exes = window_executables()
    if not exes:
        return None
    exe = exes[-1]
    if id(exe) not in _MAPS:
        ops = op_phases(exe.as_text())
        if not any(ops.values()):
            say("the window executable's HLO names no cycle.* phase; "
                f"compile cache {_cache_dir()}")
            ops = None
        _MAPS[id(exe)] = (exe, ops)
    return _MAPS[id(exe)][1]


def phase_seconds(trace, ops):
    """{phase: device seconds} of the traced ops that `ops` maps, plus
    `unscoped` for those under no phase, summed over the devices as the
    trace's op totals are; None without a trace, a map, or a traced op of
    the mapped program."""
    if not trace or not ops:
        return None
    per = defaultdict(float)
    for name, s in trace["op_totals"].items():
        key = name.lstrip("%")
        if key in ops:
            per[ops[key] or UNSCOPED] += s
    if not per:
        return None
    return dict(per)


def ms_per_lane_cycle(phase: str, run, trace):
    """Device milliseconds of `phase` per traced lane-cycle."""
    if not trace or not run.get("lane_cycles_traced"):
        return None
    per = phase_seconds(trace, window_ops())
    if per is None:
        return None
    return 1e3 * per.get(phase, 0.0) / run["lane_cycles_traced"]


def span_totals():
    """The program's host-span seconds by name, or None."""
    try:
        from repro.core.spans import totals
    except ImportError:
        return None
    return totals()


def program_report(events, ops, top: int = 10) -> dict:
    """From a profile's events (`bench.trace.load`) and an op->phase map:
    the device seconds per phase of the leaf ops inside the main
    program's runs on the first device (a trace's `phase_s`), the
    unscoped ops among them, and the idle gaps inside those runs,
    labelled by the phase of the op before and after each."""
    from bench import trace as tr
    devs = sorted({e.plane for e in events if tr.DEVICE_PLANE.match(e.plane)},
                  key=lambda p: int(p.rsplit(":", 1)[1]))
    if not devs:
        raise ValueError("the trace has no device plane")
    mods = [e for e in events
            if e.plane == devs[0] and e.line == tr.MODULES_LINE]
    per = defaultdict(float)
    for e in mods:
        per[e.name] += e.end - e.start
    main = max(per, key=per.get)
    runs = sorted((e.start, e.end) for e in mods if e.name == main)
    leaf = sorted(tr.leaves([e for e in events if e.plane == devs[0]
                             and e.line == tr.OPS_LINE]),
                  key=lambda e: e.start)

    def label(e):
        name = e.name.lstrip("%")
        return (ops[name] or UNSCOPED) if name in ops else "other"

    phase_s, unscoped, gaps = defaultdict(float), defaultdict(float), []
    i = 0
    for lo, hi in runs:
        inside = []
        while i < len(leaf) and leaf[i].start < hi:
            if leaf[i].end > lo:
                inside.append(leaf[i])
            i += 1
        last = None         # the op inside the run that ended latest
        for e in inside:
            phase_s[label(e)] += e.end - e.start
            if label(e) == UNSCOPED:
                unscoped[e.name] += e.end - e.start
            if last is not None and e.start > last.end:
                gaps.append((e.start - last.end,
                             f"{label(last)} -> {label(e)}",
                             last.name, e.name))
            if last is None or e.end > last.end:
                last = e
    gaps.sort(key=lambda g: -g[0])
    return {
        "program": main, "runs": len(runs),
        "run_s": sum(b - a for a, b in runs),
        "phase_s": dict(phase_s),
        "unscoped_ops": sorted(unscoped.items(), key=lambda kv: -kv[1])[:top],
        "program_gaps": [list(g) for g in gaps[:top]],
        "program_gap_s": sum(g[0] for g in gaps),
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        raise SystemExit("usage: python3 bench/program.py "
                         "<trace.xplane.pb> <window.hlo.txt>")
    from bench import trace as tr
    with open(argv[1]) as f:
        ops = op_phases(f.read())
    print(json.dumps(program_report(tr.load(argv[0]), ops), indent=1))
    return 0


if __name__ == "__main__":
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    # keep bench/trace.py from shadowing the stdlib; import bench.*
    sys.path[0] = os.path.dirname(here)
    sys.exit(main())
