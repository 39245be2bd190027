"""Standing perf-trajectory benchmark: the numbers every perf PR must move.

Runs a FIXED scenario set through the declarative runner —

  bench_sweep   the headline engine grid (24 lanes x 600 cycles, one
                C-group): cycles/s is compared against the frozen
                `BENCH_sweep.json` baseline AND bit-checked lane-for-lane
                against engine-sequential `Simulator.run`
  smoke         seconds-scale sanity point (tiny grid, dispatch-bound)
  fig11         the paper's radix-16 global network (reduced W-groups),
                on the OCCUPANCY-COMPACTED cycle step (`step_impl=
                "compact"`, the perf path — bit-identical to the jnp
                oracle, pinned by tests/test_compact_step.py and
                re-checked against the fused step at this scale on
                every run: `max_throughput_deviation` in the record
                must be 0.0)
  smoke_fused   the fused smoke grid dispatched with
                `REPRO_CHANNEL_SHARDS=2` — the 2-D (lanes x shards)
                placement point of the trajectory
  yield_curve   the radix-32-class warm-fault grid (2 routing cells, so
                it also exercises the multi-device cell round-robin)

and writes `BENCH_perf.json` (repo root): per-scenario cycles/s and
lanes/s, the compile/run wall split, device count, compile counts, the
device placement each scenario's grids actually ran on (`placements`,
`pad_fraction` — see docs/performance.md), and `speedup_vs_previous`
against the previous BENCH_perf.json — the trajectory every future perf
PR appends to.  Timings use the SECOND `run_experiment` call (zero
compiles, steady state); compile time is reported separately from the
first call.  A `kernels` section times the `repro.kernels.netsim`
`cycle_core` Pallas kernel standalone: interpret-mode ms/call on every
backend, plus a compiled (non-interpret) attempt.  On the TPU a failed
compiled attempt is an error; only on CPU, whose Pallas lowering only
interprets, is it recorded as `supported: false` with the error.

The bench_sweep and fig11 points double as the PERF-REGRESSION GUARD:
when a previous BENCH_perf.json of the same mode exists and either
scenario's `speedup_vs_previous` drops below 0.85, the benchmark exits
nonzero (after writing the file) unless `--allow-regression` is given —
CI fails on accidental engine slowdowns instead of silently recording
them.  Every scenario record also carries the compact-step telemetry
(`occupancy_peak` / `compact_capacity` / `superstep` / `escalations`),
so the trajectory documents how much of each ladder rung the workload
actually used.

On the CPU backend, unless already set in the environment, this
benchmark shards lanes over 4 forced host devices
(`REPRO_HOST_DEVICES=4`) and records the knob in the output, so the
committed file documents exactly how it was produced.  Export it to
override (e.g. `REPRO_HOST_DEVICES=1` for a single-device trajectory
point); other backends use their real devices.  `--fast` trims the
heavy scenarios' cycle budgets for CI smoke runs; cycles/s stays
comparable per (scenario, mode) pair, and `speedup_vs_previous` only
compares matching modes.

    python -m benchmarks.bench_perf
    python -m benchmarks.bench_perf --fast          (CI perf-smoke)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_perf.json")


def _scenarios(fast: bool):
    """(name, spec, env) triples; --fast trims the heavy grids' cycle
    budgets.  `env` is extra environment set around that scenario's runs
    (the channel-sharding knob is read per dispatch)."""
    import dataclasses

    from repro.exp import registry as SC
    out = [("bench_sweep", SC.bench_sweep_spec(), {}),
           ("smoke", SC.smoke_spec(), {})]
    fig11 = SC.get_scenario("fig11")
    yc = SC.get_scenario("yield_curve")
    if fast:
        fig11 = fig11.with_axes(warmup=50, measure=150)
        # keep the warm onset inside the trimmed run (scale with budget)
        trim_onset = 30 + 120 // 4
        faults = tuple(
            f if f.is_none else dataclasses.replace(f, onsets=(trim_onset,))
            for f in yc.axes.faults)
        yc = yc.with_axes(warmup=30, measure=120, faults=faults)
    # fig11 runs on the occupancy-compacted step — the perf path this
    # trajectory tracks (bit-identical to the oracle:
    # tests/test_compact_step.py pins compact == jnp, and
    # `_fig11_parity` below re-checks it against the fused step at this
    # scale on every benchmark run).  It runs at K=1: the sequential-
    # lane dispatch already keeps the scan body large, and unrolling
    # (REPRO_SUPERSTEP=4, parity-pinned by the same test file) measures
    # ~12% SLOWER here — the superstep's amortization only pays on
    # dispatch-bound grids, not this execution-bound one.
    fig11 = dataclasses.replace(
        fig11, routings=tuple(dataclasses.replace(r, step_impl="compact")
                              for r in fig11.routings))
    out += [("fig11", fig11, {}),
            ("smoke_fused", SC.get_scenario("smoke_fused"),
             {"REPRO_CHANNEL_SHARDS": "2"}),
            ("yield_curve", yc, {})]
    return out


def _cycles_total(spec) -> int:
    return spec.num_lanes * (spec.axes.warmup + spec.axes.measure)


def _bench_scenario(name, spec, env=None):
    from repro.exp.runner import run_experiment

    saved = {}
    for k, v in (env or {}).items():
        saved[k] = os.environ.get(k)
        os.environ[k] = v
    try:
        first = run_experiment(spec)                # compile + run
        t0 = time.perf_counter()
        steady = run_experiment(spec)               # 0 compiles
        wall = time.perf_counter() - t0
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    cyc = _cycles_total(spec)
    return steady, dict(
        lanes=spec.num_lanes,
        grids=spec.num_grids,
        cycles_per_lane=spec.axes.warmup + spec.axes.measure,
        cycles_total=cyc,
        wall_s=wall,
        compile_s=first.compile_s,
        first_call_compiles=sum(first.compile_counts),
        max_compiles_per_grid=first.max_compiles_per_grid,
        steady_compiles=sum(steady.compile_counts),
        cycles_per_s=cyc / wall,
        lanes_per_s=spec.num_lanes / wall,
        step_impl=sorted({r.step_impl for r in spec.routings}),
        grant_impl=sorted({r.grant_impl for r in spec.routings}),
        # arbitration form each grid compiled ("combined" | "two_pass");
        # a fused scenario reporting "two_pass" hit the packed-key int32
        # overflow fallback (see docs/performance.md / repro.analysis)
        grant_form=sorted({g.grant_form for g in steady.grids}),
        placements=sorted({g.placement for g in steady.grids}),
        pad_fraction=max((g.pad_fraction for g in steady.grids),
                         default=0.0),
        # compact-step telemetry (zeros / 1 on non-compact scenarios):
        # the whole-run live-row high-water mark vs the ladder rung each
        # grid compiled at, the superstep unroll, and how many grids had
        # to re-dispatch at a larger rung (should stay 0 — an escalation
        # means the starting rung is undersized for this workload)
        occupancy_peak=max((g.occupancy_peak for g in steady.grids),
                           default=0),
        compact_capacity=sorted({g.compact_capacity for g in steady.grids}),
        superstep=sorted({g.superstep for g in steady.grids}),
        escalations=sum(g.escalations for g in steady.grids),
        escalation_compiles=sum(g.escalation_compiles
                                for g in steady.grids),
    )


def _bench_sweep_parity(spec, rec, res) -> None:
    """Headline extras for bench_sweep: bit-parity vs engine-sequential
    runs and speedup vs the frozen BENCH_sweep.json cycles/s.  `res` is
    the steady `ExperimentResult` the timing pass already produced."""
    from repro.core.simulator import Simulator
    from repro.exp.runner import cells

    grid = res.grids[0]
    [cell] = list(cells(spec))
    rates, seeds = list(spec.axes.rates), list(spec.axes.seeds)
    sim = Simulator(cell.net, cell.cfg, cell.pattern)
    seq = {(r, s): sim.run(r, seed=s) for r in rates for s in seeds}
    dev = max(
        abs(seq[r, s].throughput_per_chip
            - grid.result(0, i, j).throughput_per_chip)
        / max(seq[r, s].throughput_per_chip, 1e-9)
        for i, r in enumerate(rates) for j, s in enumerate(seeds))
    rec["max_throughput_deviation"] = dev
    base_path = os.path.join(os.path.dirname(DEFAULT_OUT),
                             "BENCH_sweep.json")
    try:
        with open(base_path) as f:
            base = json.load(f)["batched_cycles_per_s"]
        rec["bench_sweep_baseline_cycles_per_s"] = base
        rec["speedup_vs_bench_sweep_baseline"] = rec["cycles_per_s"] / base
    except (OSError, KeyError, json.JSONDecodeError):
        pass


def _fig11_parity(spec, rec, res) -> None:
    """Compact-vs-fused bit-parity at fig11 scale: re-run the scenario
    on the fused step and record the max relative throughput deviation.
    The fused step is itself pinned bit-identical to the jnp oracle
    (tests/test_fused_step.py), so 0.0 here chains the compacted fig11
    counters to the oracle without paying for a paper-scale jnp run."""
    import dataclasses

    from repro.exp.runner import run_experiment

    ref_spec = dataclasses.replace(
        spec, routings=tuple(dataclasses.replace(r, step_impl="fused")
                             for r in spec.routings))
    ref = run_experiment(ref_spec)
    rates, seeds = spec.axes.rates, spec.axes.seeds
    n_faults = max(len(spec.axes.faults), 1)
    dev = max(
        abs(gr.result(f, i, j).throughput_per_chip
            - gn.result(f, i, j).throughput_per_chip)
        / max(gr.result(f, i, j).throughput_per_chip, 1e-9)
        for gr, gn in zip(ref.grids, res.grids)
        for f in range(n_faults)
        for i in range(len(rates)) for j in range(len(seeds)))
    rec["max_throughput_deviation"] = dev


def _bench_kernels(fast: bool) -> dict:
    """Standalone timing of the netsim `cycle_core` Pallas kernel on
    synthetic fused-step-shaped inputs: interpret-mode ms/call, plus a
    compiled (non-interpret) attempt.  On CPU the Pallas lowering only
    interprets, so the compiled record documents `supported: false` with
    the error; on TPU it carries the real compiled timing, and a failed
    compile is an error."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.netsim import cycle_core

    N, E = (1024, 128) if fast else (4096, 512)
    rng = np.random.default_rng(0)
    out = jnp.asarray(rng.integers(-1, E, N), jnp.int32)
    itime = jnp.asarray(rng.integers(0, 1000, N), jnp.int32)
    ok = jnp.asarray(rng.random(N) < 0.7) & (out >= 0)
    ch_ok = jnp.asarray(rng.random(E) < 0.9)
    r2 = 1 << (N - 1).bit_length()
    rec = dict(n_rows=N, n_channels=E, backend=jax.default_backend())

    def timed(interpret):
        f = jax.jit(lambda o, t, k, c: cycle_core(
            o, t, k, c, r2=r2, interpret=interpret))
        jax.block_until_ready(f(out, itime, ok, ch_ok))   # compile
        iters = 2 if fast else 5
        t0 = time.perf_counter()
        for _ in range(iters):
            res = f(out, itime, ok, ch_ok)
        jax.block_until_ready(res)
        return (time.perf_counter() - t0) / iters * 1000

    rec["interpret_ms_per_call"] = timed(True)
    try:
        rec["compiled"] = dict(supported=True, ms_per_call=timed(False))
    except Exception as e:
        if jax.default_backend() == "tpu":
            raise
        rec["compiled"] = dict(
            supported=False,
            error=f"{type(e).__name__}: {str(e)[:200]}")
    return {"netsim_cycle_core": rec}


def _previous(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}


def bench(fast: bool = False, out_path: str = DEFAULT_OUT) -> dict:
    import jax
    from repro.exp.provenance import provenance

    prev = _previous(out_path)
    prev_mode_match = prev.get("mode") == ("fast" if fast else "full")
    scenarios = {}
    for name, spec, env in _scenarios(fast):
        print(f"[bench_perf] {name}: {spec.num_lanes} lanes x "
              f"{spec.axes.warmup + spec.axes.measure} cycles ...",
              flush=True)
        steady, rec = _bench_scenario(name, spec, env)
        if name == "bench_sweep":
            _bench_sweep_parity(spec, rec, steady)
        if name == "fig11":
            _fig11_parity(spec, rec, steady)
        if prev_mode_match:
            p = prev.get("scenarios", {}).get(name)
            if p and p.get("cycles_per_s"):
                rec["prev_cycles_per_s"] = p["cycles_per_s"]
                rec["speedup_vs_previous"] = (rec["cycles_per_s"]
                                              / p["cycles_per_s"])
        scenarios[name] = rec
        print(f"[bench_perf]   {rec['cycles_per_s']:.0f} cycles/s, "
              f"{rec['wall_s']:.2f}s run + {rec['compile_s']:.2f}s "
              f"compile ({rec['first_call_compiles']} compiles, "
              f"placement {','.join(rec['placements'])})",
              flush=True)
    print("[bench_perf] kernels: netsim cycle_core ...", flush=True)
    kernels = _bench_kernels(fast)
    return dict(
        mode="fast" if fast else "full",
        device_count=len(jax.devices()),
        repro_host_devices=os.environ.get("REPRO_HOST_DEVICES"),
        scenarios=scenarios,
        kernels=kernels,
        provenance=provenance(),
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.bench_perf", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--fast", action="store_true",
                    help="trimmed cycle budgets (CI perf-smoke)")
    ap.add_argument("--out", default=None, help="output JSON path")
    ap.add_argument("--allow-regression", action="store_true",
                    help="record a bench_sweep slowdown (< 0.85x vs the "
                         "previous BENCH_perf.json) instead of exiting "
                         "nonzero")
    args = ap.parse_args(argv)
    # the CPU default, recorded in the output; env overrides.  Must happen
    # before the first repro/jax import (the knob sets XLA_FLAGS), so the
    # platform is read from JAX_PLATFORMS, not from a JAX backend
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        os.environ.setdefault("REPRO_HOST_DEVICES", "4")
    import repro  # applies the knob before jax init
    repro.use_compile_cache()
    path = os.path.abspath(args.out or DEFAULT_OUT)
    out = bench(fast=args.fast, out_path=path)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out, indent=2))
    print(f"\nwrote {path}")
    # perf-regression guard: neither the headline grid nor the fig11
    # hot path may silently slow down.  The file above is written either
    # way (the regression is recorded); only the exit status flags it.
    for guard in ("bench_sweep", "fig11"):
        spd = out["scenarios"].get(guard, {}).get("speedup_vs_previous")
        if spd is not None and spd < 0.85 and not args.allow_regression:
            print(f"[bench_perf] REGRESSION: {guard} at {spd:.3f}x of "
                  f"the previous trajectory point (< 0.85x). Pass "
                  f"--allow-regression to record it anyway.",
                  file=sys.stderr, flush=True)
            return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
