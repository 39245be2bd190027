"""Wafer-fleet Monte Carlo benchmark: yield distributions over sampled
defect maps and fault/repair schedules.

The fleet is the registered `smoke_fleet` spec (repro.exp.fleet): the
small up*/down*-routable wafer running three reliability levels — a
pristine reference, clustered wear-out that grows over two onsets and
then repairs one increment (a shrinking epoch, statically proven
restart-safe by `repro.analysis.check --spec`), and mid-run router death
with the age-based reaper draining the stranded population.  Every
sampled wafer is one sweep-seed lane, so the WHOLE fleet — 8 defect maps
fast, 128 with `--full` — runs through `BatchedSweep.run_lanes`' single
compiled dispatch per fault level grid; the per-record `compile_count`
certifies that all samples shared executables.

Writes `BENCH_fleet.json` (repo root): per (cell, level) records with
p10/p50/p90 throughput and latency over the sampled wafers, the yield
fraction against the pristine median, exact stranded max/mean, and the
reaper's drop totals.

`--serve-inbox DIR` additionally re-emits the fleet as a multi-tenant
`repro.exp.serve` inbox (one submission per wafer, one tenant each) —
the serve-scheduler stress form of the same fleet:

    python -m benchmarks.bench_fleet              (repo root, pip install -e .)
    python -m benchmarks.bench_fleet --full       (128-wafer distribution)
    python -m benchmarks.bench_fleet --serve-inbox /tmp/fleet_inbox
    PYTHONPATH=src python -m benchmarks.bench_fleet          (no install)
"""
from __future__ import annotations

import argparse
import json
import os


def bench(fast: bool = True) -> dict:
    from repro.exp.fleet import run_fleet, smoke_fleet
    from repro.exp.provenance import provenance

    fleet = smoke_fleet(fast=fast)
    res = run_fleet(fleet)
    exp = res.experiment
    spec = fleet.to_experiment()
    reaper_on = fleet.routing.reaper.park_age > 0
    # the acceptance posture: every level's samples shared one
    # executable (<= 1 compile per grid; 0 on cache reuse), and with
    # the reaper on, no run ends with an unbounded stranded population
    # unless the reaper was off
    compiles = [g.compile_count for g in exp.grids]
    return dict(
        fleet=fleet.name,
        net=fleet.topology.label,
        channels=fleet.topology.build().num_channels,
        samples=fleet.samples,
        offered_per_chip=fleet.offered,
        pattern=fleet.traffic.label,
        cycles_per_lane=fleet.warmup + fleet.measure,
        reap_age=fleet.routing.reaper.park_age,
        yield_threshold=fleet.yield_threshold,
        levels=[f.label for f in fleet.levels],
        onset_cycles=[list(f.onsets) for f in fleet.levels],
        repair_cycles=[list(f.repairs) for f in fleet.levels],
        records=res.records,
        compiles=compiles,
        reaper_on=reaper_on,
        wall_s=exp.wall_s,
        provenance=provenance(spec),
    )


def write(out: dict, path: str | None = None) -> str:
    if path is None:
        path = os.path.join(os.path.dirname(__file__), "..",
                            "BENCH_fleet.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    return os.path.abspath(path)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="128-wafer distribution (fast runs 8)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--serve-inbox", default=None, metavar="DIR",
                    help="also emit the fleet as a multi-tenant serve "
                         "inbox (one submission file per wafer)")
    args = ap.parse_args(argv)
    out = bench(fast=not args.full)
    path = write(out, args.out)
    print(json.dumps(out, indent=2))
    print(f"\nwrote {path}")
    if args.serve_inbox:
        from repro.exp.fleet import fleet_inbox, smoke_fleet
        paths = fleet_inbox(smoke_fleet(fast=not args.full),
                            args.serve_inbox)
        print(f"wrote {len(paths)} serve submissions to "
              f"{os.path.abspath(args.serve_inbox)}")
    if any(c > 1 for c in out["compiles"]):
        raise SystemExit(f"expected <= 1 compile per grid (all samples "
                         f"share executables), got {out['compiles']}")
    if out["reaper_on"]:
        bad = [r["level"] for r in out["records"]
               if r["stranded_max"] > 0 and r["reaped_total"] == 0]
        if bad:
            raise SystemExit(f"reaper enabled but levels {bad} ended "
                             f"with a stranded population and zero "
                             f"reaps")


if __name__ == "__main__":
    import repro
    repro.use_compile_cache()
    main()
