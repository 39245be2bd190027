#!/usr/bin/env python3
"""Benchmark of the flit-level simulator on the chip, one cell per run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip.  It reads the cell from `BENCHMARK.json` and
the files that names (`bench/manifest.py`), turns on the persistent
compilation cache at its fixed path, lowers the cell through the program's
own spec (`repro.exp`), opens a windowed lane session
(`BatchedSweep.start_lanes`), runs the fill, then times whole windows until
`--seconds` have passed, reads the device's memory peak, and checks every
lane against the plain reference (`bench/check.py`).

The last line on standard output is one JSON object: `correct`,
`attempted` and `failed` (lanes), `metrics`, `device`, with `--trace 1` a
`breakdown`, and last `checks`, each compared number beside its limit; the
same numbers are the last lines on standard error.  With `--trace 0` the
metrics are the cell's end-to-end ones; with `--trace 1` the per-layer ones,
read by `bench/metrics/<name>.py` from this run's record and a profiler
trace of every timed window.

Without a TPU, with fewer chips than the cell asks for, or without the
program (`src/repro`) beside this directory, it exits non-zero and prints
no result.
"""
import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse
import collections
import contextlib
import functools
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if sys.path and os.path.abspath(sys.path[0] or ".") == HERE:
    sys.path[0] = ROOT     # keep bench/trace.py from shadowing the stdlib
elif ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, manifest  # noqa: E402
from bench import trace as tracing  # noqa: E402

PEAKS = os.path.join(HERE, "peaks.json")


def refuse(msg: str):
    raise SystemExit(f"bench: {msg}")


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def import_program():
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        refuse(f"no program at {src}/repro: run from a checkout of the "
               "repository")
    if src not in sys.path:
        sys.path.insert(1, src)
    import repro
    return repro.use_compile_cache()


def devices(cell, require_tpu: bool):
    """The devices the cell runs on; refuses a host without a TPU, with
    fewer chips than the cell asks for, or of a kind `peaks.json` lacks."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        refuse(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < cell.chips:
        refuse(f"{cell.name} needs {cell.chips} chips, found {len(devs)}")
    if require_tpu:
        with open(PEAKS) as f:
            known = json.load(f)["devices"]
        if devs[0].device_kind not in known:
            refuse(f"no peaks for device kind {devs[0].device_kind!r} in "
                   f"{PEAKS}")
    return devs


def make_sweep(cell, seed: int):
    """The program's `BatchedSweep` for the cell, lowered through its own
    declarative spec."""
    from repro.core.engine import BatchedSweep
    from repro.exp import cells
    low = next(iter(cells(manifest.experiment(cell, seed))))
    return BatchedSweep(low.net, low.cfg, low.pattern)


def open_session(cell, sweep, seed: int, devs):
    tr = cell.traffic
    if tr["fill"] % tr["window"]:
        refuse(f"{cell.name}: fill {tr['fill']} is not a whole number of "
               f"{tr['window']}-cycle windows")
    lanes = [(rate, s, None) for rate, s in cell.lanes(seed)]
    # a one-chip cell on a larger host stays on one chip
    device = devs[0] if cell.chips == 1 and len(devs) > 1 else None
    return sweep.start_lanes(lanes, window=tr["window"], device=device)


@functools.cache
def _progress():
    """A jitted scalar read off a window's state: the host waits on it, not
    on the state, which the next window takes over (donates) once queued."""
    import jax
    return jax.jit(lambda state: state.stats.generated.sum())


def run_fill(sess) -> float:
    """Advance through the fill; seconds until its state is on hand.  Its
    last window is waited on as the timed ones are, so that everything
    they run is compiled here."""
    import jax
    t = time.perf_counter()
    while sess.cycle < sess.sweep.cfg.warmup:
        sess.advance()
    _progress()(sess.state).block_until_ready()
    jax.block_until_ready(sess.state)
    return time.perf_counter() - t


def run_windows(sess, seconds: float, trace_dir=None):
    """Whole windows, two at a time on the device: the next window is
    queued before the host waits for the one before it, so the host's
    wake-up after a window does not leave the device idle.  A window is
    dispatched only while under `seconds` since the first dispatch, so the
    last one ends at most two windows past it.  With a `trace_dir`, every
    window runs under the profiler, in `bench.*` host spans.  Returns (windows,
    seconds from the first dispatch until the last window's state is on
    hand)."""
    import jax
    span = jax.profiler.TraceAnnotation if trace_dir else (
        lambda name: contextlib.nullcontext())
    progress = _progress()
    pending = collections.deque()

    def dispatch():
        with span("bench.dispatch"):
            sess.advance()
            pending.append(progress(sess.state))

    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    times = []
    try:
        with span("bench.traced"):
            t0 = time.perf_counter()
            dispatch()
            while pending:
                if not sess.done() and time.perf_counter() - t0 < seconds:
                    dispatch()
                with span("bench.block"):
                    pending.popleft().block_until_ready()
                times.append(time.perf_counter() - t0)
            jax.block_until_ready(sess.state)
            wall = time.perf_counter() - t0
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    say("windows ended at " + ", ".join(f"{x:.4f}" for x in times) + " s")
    return len(times), wall


def count_compiles() -> list:
    """A one-element list that counts the backend compiles from now on."""
    import jax
    n = [0]

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return n


def memory_peak(devs, require: bool = True) -> int:
    """The allocator's peak of live buffers on the fullest device, as JAX
    reports it (`peak_bytes_in_use`).  Only a rehearsal off the chip
    (`require=False`) may find none, and reads 0."""
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" not in st:
            if require:
                refuse("the device reports no peak_bytes_in_use")
            return 0
        peaks.append(st["peak_bytes_in_use"])
    return max(peaks)


def executable_bytes(compiled) -> dict:
    m = compiled.memory_analysis()
    if m is None:
        return {}
    return {k: int(getattr(m, k + "_size_in_bytes", 0) or 0) for k in
            ("argument", "output", "temp", "generated_code")}


def main(argv=None, *, manifest_path=manifest.MANIFEST,
         require_tpu: bool = True) -> int:
    args = parse(argv)
    cell = manifest.load_cell(args.workload, manifest_path)
    try:
        cell.lanes(args.seed)
        check.require_reference(cell.config, cell.traffic)
    except ValueError as e:
        refuse(f"{cell.name}: {e}")
    cache = import_program()
    devs = devices(cell, require_tpu)
    used = devs[:cell.chips]
    say(f"cell {cell.name} seed {args.seed} lanes "
        f"{cell.lane_seeds(args.seed)} on {len(devs)} x "
        f"{devs[0].device_kind}; compile cache {cache}")

    sweep = make_sweep(cell, args.seed)
    sess = open_session(cell, sweep, args.seed, devs)
    fill_s = run_fill(sess)
    setup_s = time.perf_counter() - T_START
    say(f"set-up {setup_s:.3f} s: compile {sess.compile_s:.3f} s, fill "
        f"{fill_s:.3f} s to cycle {sess.cycle}; placement {sess.placement}, "
        f"step {sess.step_impl}, grant {sess.grant_form}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    compiles = count_compiles()
    try:
        start = sess.cycle
        windows, wall = run_windows(sess, args.seconds, trace_dir)
        in_window = compiles[0]
        lanes = sess.num_lanes
        lane_cycles = lanes * (sess.cycle - start)
        peak = memory_peak(used, require_tpu)
        exe = executable_bytes(sess.compiled)
        compile_s = sess.compile_s
        got = check.program_records(sess.state, lanes)
        cycles = sess.cycle
        red = None
        if args.trace:
            red = tracing.reduce(tracing.load(tracing.find(trace_dir)))
            say(f"trace: {red['modules']} program runs over "
                f"{red['window'][1] - red['window'][0]:.4f} s, busy "
                f"{red['busy_s']:.4f} s, gaps between runs "
                f"{red['module_gaps']}")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    sess = sweep = None        # the program's state goes before the check
    say(f"timed {windows} windows, {lane_cycles} lane-cycles in {wall:.4f} "
        f"s to cycle {cycles}, {in_window} compiles in the window; peak "
        f"{peak} B; executable {exe}; memory {used[0].memory_stats()}")

    t = time.perf_counter()
    lane_pairs = cell.lanes(args.seed)
    want = check.reference_records(cell.config, cell.traffic, lane_pairs,
                                   cycles)
    numbers, failed = check.compare(got, want)
    correct = (check.verdict(numbers) and windows >= 1
               and len(got) == len(want) == len(lane_pairs))
    say(f"reference replayed {lanes} lanes to cycle {cycles} in "
        f"{time.perf_counter() - t:.1f} s")

    run = {"compile_s": compile_s, "fill_s": fill_s,
           "lane_cycles_traced": lane_cycles if args.trace else 0}
    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            value = manifest.metric_reader(m["name"])(run, red)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"setup_s": setup_s, "lane_cycles_per_s": lane_cycles / wall}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": lanes,
              "failed": failed, "metrics": metrics, "device": device}
    if red is not None:
        lo, hi = red["window"]
        device.update(busy_s=red["busy_s"], window_s=hi - lo)
        result["breakdown"] = tracing.breakdown(red)
    result["checks"] = check.as_json(numbers)
    for line in check.lines(numbers):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
