#!/usr/bin/env python3
"""Readings that the correctness limits are set from, for one cell.

    python3 bench/calibrate.py --workload <name> --seeds 11,12,13 \
        [--windows 2] [--control 3] [--out <file.json>]

In one process that holds the chip: for each seed, the cell's session is
opened, filled and advanced `--windows` windows through the timed path,
and the three compared numbers of `bench/check.py` are read against the
plain reference that covers the cell (`bench/references/`).  For the
first `--control` seeds the control (that reference with its statistics
one precision lower) is read as well.  The benchmark's own runs never run
the control.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if sys.path and os.path.abspath(sys.path[0] or ".") == HERE:
    sys.path[0] = ROOT
elif ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, manifest, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--windows", type=int, default=2)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = manifest.load_cell(args.workload)
    run.import_program()
    devs = run.devices(cell, require_tpu=True)
    sweep = run.make_sweep(cell, seeds[0])
    rows = []
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        sess = run.open_session(cell, sweep, seed, devs)
        run.run_fill(sess)
        for _ in range(args.windows):
            sess.advance()
        got = check.program_records(sess.state, sess.num_lanes)
        cycles = sess.cycle
        sess = None
        lanes = cell.lanes(seed)
        want = check.reference_records(cell.config, cell.traffic, lanes,
                                       cycles)
        row = {"seed": seed, "cycles": cycles,
               "program": check.compare(got, want)[0]}
        if i < args.control:
            low = check.reference_records(cell.config, cell.traffic, lanes,
                                          cycles, control=True)
            row["control"] = check.compare(low, want)[0]
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {k: {"program_max": max(r["program"][k] for r in rows),
                   "control_min": min((r["control"][k] for r in rows
                                       if "control" in r), default=None),
                   "limit": check.LIMITS[k]} for k in check.LIMITS}
    print(json.dumps({"workload": cell.name, "summary": summary}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": cell.name, "rows": rows,
                       "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
