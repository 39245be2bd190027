#!/usr/bin/env python3
"""Bring-up smoke run of the simulator on a TPU.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # lane and channel sharding, 4 chips

One process drives the chip.  The default run takes the paper's
radix-16 switch-less fabric at its published size (`fig11_spec(fast=False)`:
g=41 W-groups, 1,312 chips, 30,176 channels), cut to one topology, one
traffic and 4 lanes of 400 cycles, through `run_experiment` on the
three step forms:

  jnp              the phase-pipeline oracle
  compact          the occupancy-compacted step
  fused + pallas   the fused step granting through the compiled
                   `cycle_core` Pallas kernel

Every integer counter of every lane must equal the oracle's bit for
bit; a windowed session of the Pallas form checks exact packet
conservation over the measured window; and the registered `smoke`
scenario must give the same counters on the chip and on the host CPU.
`--four-chips` instead runs the same 4 lanes, for 200 cycles,
lane-sharded over four chips and channel-sharded
(`REPRO_CHANNEL_SHARDS=4`) on the fused step, against the lanes pinned
to one chip.

The cycle budgets are cut for time: the `jnp` oracle runs at ~16
lane-cycles/s on one v5e chip at this size, so the whole default run
stays near 6 minutes with a cold compile cache.

Each phase prints a line; the last line is one JSON object naming the
device.  Any failed check exits non-zero, and so does a run that finds
no TPU or no `src/repro` next to this file.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
RATES = (0.4, 1.0)
SEEDS = (0, 1)
CYCLES = (100, 300)        # (warmup, measure) of the default run
FOUR_CHIP_CYCLES = (50, 150)


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


def say(label: str, **kw) -> None:
    print(f"chip_smoke: {label}: " + " ".join(f"{k}={v}" for k, v in
                                              kw.items()), flush=True)


def counters(r) -> tuple:
    """Every integer counter of one lane's `SimResult` (the latency sum
    enters through its exact quotient `avg_latency`)."""
    return (r.generated_pkts, r.delivered_pkts, r.dropped_pkts,
            r.reaped_pkts, r.stranded_pkts, r.occupancy_peak,
            tuple(sorted(r.hops_by_type.items())), r.avg_latency)


def lanes_of(grid) -> list:
    return [grid.result(0, ri, si) for ri in range(len(grid.rates))
            for si in range(len(grid.seeds))]


def same_lanes(name: str, ref: list, got: list) -> None:
    check(len(ref) == len(got), f"{name}: {len(got)} lanes, want {len(ref)}")
    for i, (a, b) in enumerate(zip(ref, got)):
        check(counters(a) == counters(b),
              f"{name}: lane {i} differs from the reference:\n"
              f"  ref {counters(a)}\n  got {counters(b)}")


def cut_spec(step_impl="jnp", grant_impl="jnp", base=None, cycles=CYCLES):
    """The smoke cut of `base` (default: the published g=41 fig11
    scenario): its first topology and traffic, minimal routing with 2
    VCs per class, rates x seeds = 4 lanes, `cycles` = (warmup,
    measure).  The short budget also keeps the packed grant key inside
    int32 (`grant_form` "combined"), which the Pallas kernel needs."""
    from repro.exp import RoutingSpec, SweepAxes
    from repro.exp.registry import fig11_spec
    full = base or fig11_spec(fast=False)
    return dataclasses.replace(
        full, name=f"{full.name}_chip_smoke",
        topologies=full.topologies[:1], traffics=full.traffics[:1],
        routings=(RoutingSpec(route_mode="min", vcs_per_class=2,
                              step_impl=step_impl, grant_impl=grant_impl),),
        axes=SweepAxes(rates=RATES, seeds=SEEDS, warmup=cycles[0],
                       measure=cycles[1]))


def print_cuts(full, cut) -> None:
    def lanes(s):
        return (len(s.topologies) * len(s.traffics) * len(s.routings)
                * s.axes.lanes_per_grid)
    net = cut.topologies[0].build()
    say("cuts", scenario=full.name,
        topologies=f"{len(full.topologies)}->{len(cut.topologies)}",
        traffics=f"{len(full.traffics)}->{len(cut.traffics)}",
        lanes=f"{lanes(full)}->{lanes(cut)}",
        cycles=f"{full.axes.warmup + full.axes.measure}->"
               f"{cut.axes.warmup + cut.axes.measure}",
        chips=net.num_chips, channels=net.num_channels,
        terminals=net.num_terminals)


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_form(label: str, spec, device) -> list:
    """Run `spec` (one grid) through `run_experiment` once and return
    its lanes.  The engine compiles ahead of time, so the grid's
    `wall_s` (execution up to the blocking read of the result) already
    excludes `compile_s`; a second, steady-state call would double the
    chip time for the same number."""
    from repro.exp import run_experiment
    t0 = time.perf_counter()
    grids = run_experiment(spec).grids
    call_s = time.perf_counter() - t0
    check(len(grids) == 1, f"{label}: {len(grids)} grids, want 1")
    g = grids[0]
    ref = lanes_of(g)
    cycles = spec.axes.warmup + spec.axes.measure
    say(label, compile_s=f"{g.compile_s:.2f}", wall_s=f"{g.wall_s:.3f}",
        call_s=f"{call_s:.3f}",
        lane_cycles_per_s=f"{len(ref) * cycles / g.wall_s:.1f}",
        placement=g.placement, grant_form=g.grant_form,
        compact_capacity=g.compact_capacity, escalations=g.escalations,
        peak_bytes_in_use=peak_bytes(device))
    for r in ref:
        check(r.throughput_per_chip > 0,
              f"{label}: zero throughput at offered {r.offered_per_chip}")
    return ref


def kernel_in(executable) -> bool:
    """Whether a compiled executable holds a Mosaic kernel (a Pallas
    call that was compiled, not interpreted or replaced)."""
    return "tpu_custom_call" in executable.as_text()


def conservation(label: str, spec, ref: list) -> None:
    """Replay the grid as a windowed session that pauses right after the
    warmup's stats reset, and check exact conservation of the measured
    window: generated == delivered + dropped + reaped + (in-flight at
    the end - in-flight at the reset).  The finished session must equal
    `ref` lane for lane; its executable must hold the kernel."""
    import numpy as np
    from repro.core.engine import BatchedSweep
    from repro.exp import cells
    cell = next(iter(cells(spec)))
    sweep = BatchedSweep(cell.net, cell.cfg, cell.pattern)
    lanes = [(r, s, None) for r in spec.axes.rates for s in spec.axes.seeds]
    sess = sweep.start_lanes(lanes, window=spec.axes.warmup + 1)

    def inflight():
        st = sess.export()["state"]
        return (st.b_count.reshape(len(lanes), -1).sum(1)
                + st.s_count.reshape(len(lanes), -1).sum(1))

    sess.advance()
    at_reset = inflight()
    while not sess.done():
        sess.advance()
    held = inflight() - at_reset
    run = sess.finish()
    same_lanes(f"{label} session", ref, run.results)
    for i, r in enumerate(run.results):
        check(r.generated_pkts == r.delivered_pkts + r.dropped_pkts
              + r.reaped_pkts + int(held[i]),
              f"{label}: lane {i} leaks packets: generated "
              f"{r.generated_pkts} != delivered {r.delivered_pkts} + dropped "
              f"{r.dropped_pkts} + reaped {r.reaped_pkts} + in-flight change "
              f"{int(held[i])}")
    custom = kernel_in(sess.compiled)
    say(f"{label} conservation", lanes=len(lanes),
        inflight_change=np.asarray(held).tolist(), tpu_custom_call=custom)
    check(custom, f"{label}: no tpu_custom_call in the compiled step; the "
                  "cycle_core kernel was interpreted or replaced")


def smoke_on_cpu_and_device() -> None:
    """The registered `smoke` scenario on the default device, and the
    same lanes on the host CPU backend: the counters must agree."""
    import jax
    from repro.core.engine import BatchedSweep
    from repro.exp import cells, get_scenario, run_experiment
    spec = get_scenario("smoke")
    grid = run_experiment(spec).grids[0]
    cell = next(iter(cells(spec)))
    lanes = [(r, s, None) for r in spec.axes.rates for s in spec.axes.seeds]
    cpu = jax.devices("cpu")[0]
    run = BatchedSweep(cell.net, cell.cfg, cell.pattern).run_lanes(
        lanes, device=cpu)
    same_lanes("smoke cpu vs device", lanes_of(grid), run.results)
    say("smoke", lanes=len(lanes), device=jax.devices()[0].device_kind,
        cpu=cpu.platform, equal=True)


def one_chip(base=None) -> None:
    import jax
    dev = jax.devices()[0]
    oracle = run_form("jnp", cut_spec("jnp", base=base), dev)
    compact = run_form("compact", cut_spec("compact", base=base), dev)
    same_lanes("compact vs jnp", oracle, compact)
    pallas_spec = cut_spec("fused", "pallas", base=base)
    pallas = run_form("fused+pallas", pallas_spec, dev)
    same_lanes("fused+pallas vs jnp", oracle, pallas)
    say("parity", forms="compact,fused+pallas", reference="jnp",
        lanes=len(oracle), equal=True)
    conservation("fused+pallas", pallas_spec, oracle)
    smoke_on_cpu_and_device()


def four_chips(base=None) -> None:
    """Lane sharding (`lanes:4`) and 2-D channel sharding
    (`lanes:1,shards:4`) of the fused step against the same lanes pinned
    to device 0."""
    import jax
    from repro.core.engine import BatchedSweep
    from repro.exp import cells, run_experiment
    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    spec = cut_spec("fused", base=base, cycles=FOUR_CHIP_CYCLES)
    cycles = spec.axes.warmup + spec.axes.measure
    cell = next(iter(cells(spec)))
    lanes = [(r, s, None) for r in spec.axes.rates for s in spec.axes.seeds]
    pinned = BatchedSweep(cell.net, cell.cfg, cell.pattern).run_lanes(
        lanes, device=devs[0])
    say("fused pinned", placement=pinned.placement,
        compile_s=f"{pinned.compile_s:.2f}", wall_s=f"{pinned.wall_s:.3f}")
    check(pinned.placement == "single", "the reference left device 0")
    # 4 lanes x 200 cycles is under the engine's min-work gate for lane
    # sharding; lower it so the short budget still takes the sharded path
    os.environ["REPRO_SHARD_MIN_WORK"] = "0"
    os.environ.pop("REPRO_CHANNEL_SHARDS", None)
    for label, shards, want in (("lane-sharded", None, "lanes:4"),
                                ("channel-sharded", "4", "lanes:1,shards:4")):
        if shards:
            os.environ["REPRO_CHANNEL_SHARDS"] = shards
        t0 = time.perf_counter()
        grid = run_experiment(spec).grids[0]
        call_s = time.perf_counter() - t0
        os.environ.pop("REPRO_CHANNEL_SHARDS", None)
        say(label, placement=grid.placement,
            compile_s=f"{grid.compile_s:.2f}", wall_s=f"{grid.wall_s:.3f}",
            call_s=f"{call_s:.3f}",
            lane_cycles_per_s=f"{len(lanes) * cycles / grid.wall_s:.1f}",
            peak_bytes_in_use=[peak_bytes(d) for d in devs])
        check(grid.placement == want,
              f"{label}: placement {grid.placement}, want {want}")
        same_lanes(f"{label} vs pinned", pinned.results, lanes_of(grid))
    say("parity", forms="lane-sharded,channel-sharded",
        reference="fused pinned to device 0", lanes=len(lanes), equal=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the lane- and channel-sharded paths "
                         "over four chips")
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no src/repro next to {os.path.basename(__file__)}; run it "
             "from a checkout of the repository")
    sys.path.insert(0, src)
    import repro
    cache = repro.use_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {devs[0].platform} devices")
    from repro.exp.provenance import provenance
    from repro.exp.registry import fig11_spec
    say("device", **{k: v for k, v in provenance().items()},
        compile_cache=cache)
    print_cuts(fig11_spec(fast=False), cut_spec(
        cycles=FOUR_CHIP_CYCLES if args.four_chips else CYCLES))
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)()
    say("done", total_s=f"{time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
