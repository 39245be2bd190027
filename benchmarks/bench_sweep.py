"""Load-latency sweep benchmark: seed baseline vs. the batch-parallel engine.

Times the same (rate x seed) sweep three ways on a small switch-less config
(the registered `bench_sweep` scenario — every configuration here comes
from its `ExperimentSpec`, see repro.exp):

  seed        the frozen PR-0 monolithic simulator (`seed_reference.py`),
              one jitted `lax.scan` per lane — what the paper-figure grid
              cost before this engine existed
  sequential  the modular engine, still one scan per lane (`Simulator.run`)
  batched     all lanes lowered through `run_experiment` into ONE jitted
              scan (`BatchedSweep.run_lanes`)

and writes `BENCH_sweep.json` (repo root).  The headline `speedup` is
batched vs. the seed baseline — the wall-clock the refactor actually bought
(packed packet records, request-grid slicing, dense credit/busy/stats
updates, plus whole-sweep batching); `speedup_vs_engine_sequential` isolates
the batching itself.  `max_throughput_deviation` checks that the batched
lanes reproduce per-rate sequential runs (they are bit-identical by
construction).

    python -m benchmarks.bench_sweep            (repo root, pip install -e .)
    PYTHONPATH=src python -m benchmarks.bench_sweep        (no install)
"""
from __future__ import annotations

import json
import os
import time

DEFAULT_RATES = (0.2, 0.4, 0.6, 0.8, 1.0, 1.2, 1.4, 1.6)
DEFAULT_SEEDS = (0, 1, 2)


def bench(rates=DEFAULT_RATES, seeds=DEFAULT_SEEDS,
          warmup=100, measure=500) -> dict:
    from repro.core.simulator import Simulator
    from repro.exp import registry as SC
    from repro.exp.provenance import provenance
    from repro.exp.runner import cells, run_experiment
    from benchmarks.seed_reference import SeedSimulator

    spec = SC.bench_sweep_spec(rates=rates, seeds=seeds,
                               warmup=warmup, measure=measure)
    [cell] = list(cells(spec))   # one (topology, routing, traffic) grid
    rates, seeds = list(spec.axes.rates), list(spec.axes.seeds)
    lanes = len(rates) * len(seeds)
    cycles_total = (warmup + measure) * lanes

    # --- batched: the declarative lowering, whole sweep in one scan ---
    res = run_experiment(spec)                    # compile + run
    compile_s = res.compile_s                     # exact split (AOT cache)
    first_wall = res.wall_s
    first_compiles = res.max_compiles_per_grid
    res = run_experiment(spec)                    # steady-state timing
    t_batched = res.wall_s
    grid = res.grids[0]

    # --- engine sequential: one scan per lane -------------------------
    sim = Simulator(cell.net, cell.cfg, cell.pattern)
    sim.run(rates[0], seed=seeds[0])              # compile
    t0 = time.perf_counter()
    seq = {(r, s): sim.run(r, seed=s) for r in rates for s in seeds}
    t_seq = time.perf_counter() - t0

    # --- seed baseline: the pre-engine monolithic simulator -----------
    seed_sim = SeedSimulator(cell.net, cell.cfg, cell.pattern)
    seed_sim.run(rates[0])                        # compile
    t0 = time.perf_counter()
    for r in rates:
        for _ in seeds:
            seed_sim.run(r)
    t_seed = time.perf_counter() - t0

    max_dev = max(
        abs(seq[r, s].throughput_per_chip
            - grid.result(0, i, j).throughput_per_chip)
        / max(seq[r, s].throughput_per_chip, 1e-9)
        for i, r in enumerate(rates) for j, s in enumerate(seeds))

    return dict(
        net="switchless a=1 b=1 m=2 n=6 (one C-group)",
        scenario=spec.name,
        channels=cell.net.num_channels,
        rates=rates, seeds=seeds, lanes=lanes,
        cycles_per_lane=warmup + measure,
        seed_sequential_wall_s=t_seed,
        engine_sequential_wall_s=t_seq,
        batched_wall_s=t_batched,
        batched_first_call_s=first_wall + compile_s,
        batched_compile_s=compile_s,
        speedup=t_seed / t_batched,                 # headline: vs PR-0 seed
        speedup_vs_engine_sequential=t_seq / t_batched,
        batched_cycles_per_s=cycles_total / t_batched,
        seed_cycles_per_s=cycles_total / t_seed,
        first_call_compiles=first_compiles,         # 1: one compile per grid
        batched_compiles=grid.compile_count,        # 0: cache-hit on 2nd call
        max_throughput_deviation=max_dev,
        provenance=provenance(spec),
    )


def write(out: dict, path: str | None = None) -> str:
    if path is None:
        path = os.path.join(os.path.dirname(__file__), "..",
                            "BENCH_sweep.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    return os.path.abspath(path)


def main() -> None:
    out = bench()
    path = write(out)
    print(json.dumps(out, indent=2))
    print(f"\nwrote {path}")


if __name__ == "__main__":
    import repro
    repro.use_compile_cache()
    main()
