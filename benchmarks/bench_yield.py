"""Yield-vs-throughput benchmark: delivered throughput vs. the fraction of
global links lost MID-RUN, minimal vs. fault-aware adaptive routing.

Wafer-scale yield analyses price a design by how gracefully it degrades as
links die, and warm faults are the realistic form: the link dies while
traffic is in flight, buffered packets must drain over the survivors.  The
grid is the registered `yield_curve` scenario (repro.exp): the paper's
radix-32-class switch-less network (2B on-wafer bandwidth), adversarial
worst-case traffic, and a `FaultSpec` schedule per fault fraction that
kills the links a quarter of the way into the measurement window.  Two
routings run as separate grids of ONE spec — minimal, and UGAL with the
fault-aware adaptive misroute stage (alive-masked candidates, sensors on
surviving links, degradation bias) — each grid one compiled batched scan.

Writes `BENCH_yield.json` (repo root).  The headline check is
`adaptive_ge_minimal`: adaptive routing must deliver at least minimal
routing's throughput at EVERY nonzero fault fraction (it re-routes around
the dead parallel links; minimal can only re-pick among survivors of the
same W-group pair).

    python -m benchmarks.bench_yield            (repo root, pip install -e .)
    python -m benchmarks.bench_yield --full     (paper-scale g=9 grid)
    PYTHONPATH=src python -m benchmarks.bench_yield        (no install)
"""
from __future__ import annotations

import argparse
import json
import os


def bench(fast: bool = True) -> dict:
    from repro.exp import registry as SC
    from repro.exp.provenance import provenance
    from repro.exp.runner import run_experiment

    spec = SC.yield_curve_spec(fast=fast)
    res = run_experiment(spec)
    # cells iterate routing-major inside one topology: grids[0] = minimal,
    # grids[1] = adaptive (the spec's routing order)
    by_mode = {g.routing.route_mode: g for g in res.grids}
    gmin, gada = by_mode["min"], by_mode["ugal"]
    fault_labels = gmin.fault_labels
    fracs = gmin.fault_fracs
    curves = {}
    for tag, grid in (("minimal", gmin), ("adaptive", gada)):
        curves[tag] = dict(
            throughput=[row[0].throughput_per_chip
                        for row in (grid.sweep_result(fi).mean_over_seeds()
                                    for fi in range(len(fault_labels)))],
            latency=[grid.sweep_result(fi).mean_over_seeds()[0].avg_latency
                     for fi in range(len(fault_labels))],
            delivered_pkts=[[grid.result(fi, 0, si).delivered_pkts
                             for si in range(len(grid.seeds))]
                            for fi in range(len(fault_labels))],
            # exact per-seed stranded populations at exit plus the
            # seed-aggregated view (max + exact mean, the
            # mean_over_seeds convention)
            stranded_pkts=[[grid.result(fi, 0, si).stranded_pkts
                            for si in range(len(grid.seeds))]
                           for fi in range(len(fault_labels))],
            stranded_max=[grid.sweep_result(fi).mean_over_seeds()[0]
                          .stranded_pkts
                          for fi in range(len(fault_labels))],
            stranded_mean=[grid.sweep_result(fi).mean_over_seeds()[0]
                           .stranded_mean
                           for fi in range(len(fault_labels))],
            compiles=grid.compile_count)
    # the acceptance check: adaptive >= minimal at every NONZERO fraction
    # (at zero both route minimally modulo sensor noise)
    ok = all(a >= m for a, m, f in zip(curves["adaptive"]["throughput"],
                                       curves["minimal"]["throughput"],
                                       fracs) if f > 0)
    return dict(
        scenario=spec.name,
        net=gmin.topology.label,
        channels=gmin.topology.build().num_channels,
        offered_per_chip=spec.axes.rates[0],
        pattern=gmin.traffic.label,
        seeds=list(spec.axes.seeds),
        cycles_per_lane=spec.axes.warmup + spec.axes.measure,
        fault_labels=fault_labels,
        fault_fracs=fracs,
        onset_cycles=[list(f.onsets) for f in spec.axes.faults],
        minimal=curves["minimal"],
        adaptive=curves["adaptive"],
        adaptive_ge_minimal=ok,
        compiles=[g.compile_count for g in res.grids],
        wall_s=res.wall_s,
        provenance=provenance(spec),
    )


def write(out: dict, path: str | None = None) -> str:
    if path is None:
        path = os.path.join(os.path.dirname(__file__), "..",
                            "BENCH_yield.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    return os.path.abspath(path)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale grid (g=9, long cycles)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    out = bench(fast=not args.full)
    path = write(out, args.out)
    print(json.dumps(out, indent=2))
    print(f"\nwrote {path}")
    if any(c > 1 for c in out["compiles"]):
        raise SystemExit(f"expected <= 1 compile per grid, got "
                         f"{out['compiles']}")
    if not out["adaptive_ge_minimal"]:
        raise SystemExit("adaptive misrouting fell below minimal routing "
                         "on the degraded network")


if __name__ == "__main__":
    import repro
    repro.use_compile_cache()
    main()
