"""Degraded-wafer throughput benchmark: accepted throughput and latency
vs. the fraction of failed fabric links.

Wafer-scale integration makes dead links/routers the norm (known-good-die
yield, post-bond defects), so the interesting number is not peak throughput
but how gracefully the switch-less fabric degrades.  The grid is the
registered `bench_faults` scenario (repro.exp): one independently sampled
link-failure `FaultSpec` population per failure rate, one lane per
(failure rate, seed) with per-lane fault-aware routing tables, the WHOLE
grid lowered to ONE compiled batched scan (`BatchedSweep.run_lanes` stacks
the per-lane fault tables and vmaps the shared step over them) —
`compiles == 1` in the output is the proof.

Writes `BENCH_faults.json` (repo root) with the per-rate seed-averaged
curve; `monotone_within_tol` checks that accepted throughput never
*increases* materially as more links fail.

    python -m benchmarks.bench_faults           (repo root, pip install -e .)
    PYTHONPATH=src python -m benchmarks.bench_faults       (no install)
"""
from __future__ import annotations

import json
import os

DEFAULT_FRACS = (0.0, 0.04, 0.08, 0.12, 0.16)
DEFAULT_SEEDS = (0, 1)
# a shade above the pristine saturation point, so accepted throughput
# tracks the surviving capacity instead of the offered load
DEFAULT_OFFERED = 0.55
MONOTONE_TOL = 0.03   # allowed non-monotone wiggle (flits/cycle/chip)


def bench(fracs=DEFAULT_FRACS, seeds=DEFAULT_SEEDS,
          offered=DEFAULT_OFFERED, warmup=300, measure=1500) -> dict:
    from repro.exp import registry as SC
    from repro.exp.provenance import provenance
    from repro.exp.runner import run_experiment

    spec = SC.bench_faults_spec(fracs=fracs, seeds=seeds, offered=offered,
                                warmup=warmup, measure=measure)
    res = run_experiment(spec)
    [grid] = res.grids
    fracs, seeds = list(fracs), list(seeds)

    rows = res.rows()
    thr = [r["throughput"] for r in rows]
    lat = [r["latency"] for r in rows]
    monotone = all(thr[i + 1] <= thr[i] + MONOTONE_TOL
                   for i in range(len(thr) - 1))
    return dict(
        net="switchless a=2 b=2 m=2 n=4 g=5 (updown, minimal)",
        scenario=spec.name,
        channels=grid.topology.build().num_channels,
        offered_per_chip=offered,
        requested_fracs=fracs,
        achieved_fracs=grid.fault_fracs,
        seeds=seeds,
        lanes=len(fracs) * len(seeds),
        cycles_per_lane=warmup + measure,
        throughput_per_chip=thr,
        avg_latency=lat,
        per_seed_throughput=[[grid.result(i, 0, j).throughput_per_chip
                              for j in range(len(seeds))]
                             for i in range(len(fracs))],
        delivered_pkts=[[grid.result(i, 0, j).delivered_pkts
                         for j in range(len(seeds))]
                        for i in range(len(fracs))],
        compiles=grid.compile_count,
        wall_s=res.wall_s,
        monotone_within_tol=monotone,
        monotone_tol=MONOTONE_TOL,
        provenance=provenance(spec),
    )


def write(out: dict, path: str | None = None) -> str:
    if path is None:
        path = os.path.join(os.path.dirname(__file__), "..",
                            "BENCH_faults.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    return os.path.abspath(path)


def main() -> None:
    out = bench()
    path = write(out)
    print(json.dumps(out, indent=2))
    print(f"\nwrote {path}")
    if out["compiles"] != 1:
        raise SystemExit(f"expected exactly 1 compile, got {out['compiles']}")
    if not out["monotone_within_tol"]:
        raise SystemExit("degraded-throughput curve is not monotone")


if __name__ == "__main__":
    import repro
    repro.use_compile_cache()
    main()
