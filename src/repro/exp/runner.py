"""Lower an `ExperimentSpec` onto the batch-parallel engine.

`run_experiment(spec)` iterates the outer-product cells
(topology x routing x traffic) and runs each cell's whole lane grid
(faults x rates x seeds) as ONE `BatchedSweep.run_lanes` dispatch — at
most one jit compile per grid, by construction.  Cells that share an
identical step function (same topology, routing, traffic, and cycle
budget) reuse one compiled `BatchedSweep` through a process-wide cache,
so re-running a spec (or running a spec that overlaps an earlier one)
costs zero new compiles.

Device parallelism: with one device (the un-forced CPU default) cells
run serially and each grid shards nothing.  With multiple devices
(`REPRO_HOST_DEVICES=N`, or a real multi-device backend) a single-cell
spec shard_maps its lane axis over the whole mesh, while a multi-cell
spec ROUND-ROBINS cells across devices instead: every cell's grid is
dispatched asynchronously to device `i % ndev` and materialized
afterwards, so independent grids execute concurrently (dispatch is
async; only compilation serializes on the host).  Cells on
paper-scale networks (more channels than `REPRO_RR_MAX_CHANNELS`,
default 1024) opt out of the round-robin and run serially on the
default device — overlapped execution of multi-MB channel states
thrashes shared caches and measures SLOWER than serial (see
`rr_max_channels`).  Either way results are lane-for-lane identical to
the serial single-device run — device placement never changes per-lane
math.

`cells(spec)` exposes the same lowering without running anything — the
hook benchmarks use to build sequential/legacy baselines from the exact
(net, cfg, pattern) a spec denotes.
"""
from __future__ import annotations

import sys
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

from .. import env_int
from ..core.engine.sweep import BatchedSweep, SweepResult
from ..core.simulator import SimConfig, SimResult
from ..core.topology import Network, final_faults
from ..core.traffic import TrafficPattern
from .spec import (ExperimentSpec, FaultSpec, RoutingSpec, SweepAxes,
                   TopologySpec, TrafficSpec)

# Process-wide compiled-sweep cache: one `BatchedSweep` (hence one engine
# step closure, hence one jit cache entry) per distinct cell.  Keyed by
# the specs themselves — they are frozen/hashable, that's the point.
_SWEEP_CACHE: dict = {}
# Sampled fault sets, keyed by (topology, fault spec, vc_mode, lane seed):
# greedy-validated sampling is the slow part, and the same population is
# reused across every routing/traffic cell that shares a vc_mode.
_FAULT_CACHE: dict = {}


def rr_max_channels() -> int:
    """`REPRO_RR_MAX_CHANNELS` (default 1024): cells whose network has
    more channels than this run on the DEFAULT device serially instead
    of round-robining.  Round-robin overlaps independent grids, which
    pays off for small cells; for paper-scale networks the concurrently
    executing cells evict each other's multi-MB channel state from
    cache and the 'parallel' run comes out slower than the serial one
    (fig11 measured ~20% slower round-robined on forced host devices).
    The per-cell decision is visible in `GridResult.placement` /
    `run_experiment(verbose=True)`."""
    return env_int("REPRO_RR_MAX_CHANNELS", 1024)


def clear_caches() -> None:
    """Drop the compiled-sweep, fault-sample, and built-network caches
    (tests / memory)."""
    from . import spec as _spec
    _SWEEP_CACHE.clear()
    _FAULT_CACHE.clear()
    _spec._NET_CACHE.clear()


class Cell(NamedTuple):
    """One lowered outer-product cell of an experiment."""

    topology: TopologySpec
    routing: RoutingSpec
    traffic: TrafficSpec
    net: Network
    cfg: SimConfig
    pattern: TrafficPattern


def cells(spec: ExperimentSpec):
    """Yield the lowered (net, cfg, pattern) cells of `spec`, in run
    order (topology-major, then routing, then traffic)."""
    for topo in spec.topologies:
        net = topo.build()
        for routing in spec.routings:
            cfg = routing.to_simconfig(spec.axes)
            for traffic in spec.traffics:
                yield Cell(topo, routing, traffic, net, cfg,
                           traffic.resolve(net))


@dataclass
class GridResult:
    """One cell's (faults x rates x seeds) grid of `SimResult`s."""

    topology: TopologySpec
    routing: RoutingSpec
    traffic: TrafficSpec
    rates: list
    seeds: list
    fault_labels: list          # [F]
    fault_fracs: list           # [F] mean failed-link fraction over seeds
    results: list               # [F][R][S] of SimResult
    step_impl: str              # cycle-step form the grid compiled
    route_cached: bool          # heads read the route-once cache (false
                                # for the oracle and warm-fault lanes)
    compile_count: int = 0
    wall_s: float = 0.0         # execution wall (compile excluded); for
                                # round-robined cells this spans dispatch
                                # -> materialized, overlapping other cells
    compile_s: float = 0.0      # trace+compile wall (0.0 on cache reuse)
    placement: str = "single"   # device layout the grid actually ran on
                                # ("single" | "lanes:L" | "lanes:L,shards:K")
    pad_fraction: float = 0.0   # ghost fraction of the dispatched
                                # lane x channel grid (placement padding)
    grant_form: str = "two_pass"   # arbitration form the grid compiled
                                # ("combined" | "two_pass"; fused steps
                                # fall back to two_pass on int32 packed-
                                # key overflow — see fused.grant_form)
    occupancy_peak: int = 0     # max live request rows over the grid
    compact_capacity: int = 0   # compact ladder rung (0 = dense step)
    superstep: int = 1          # K-cycle unroll the grid compiled
    escalations: int = 0        # capacity-ladder reruns (compact step)
    escalation_compiles: int = 0   # compiles spent on abandoned rungs
                                   # (kept out of compile_count: each
                                   # rung is its own executable)

    def result(self, fault_idx: int, rate_idx: int,
               seed_idx: int = 0) -> SimResult:
        return self.results[fault_idx][rate_idx][seed_idx]

    def sweep_result(self, fault_idx: int = 0) -> SweepResult:
        """One fault row as a legacy `SweepResult` (rate x seed grid)."""
        return SweepResult(rates=list(self.rates), seeds=list(self.seeds),
                           results=self.results[fault_idx],
                           step_impl=self.step_impl,
                           route_cached=self.route_cached,
                           compile_count=self.compile_count,
                           wall_s=self.wall_s, placement=self.placement,
                           pad_fraction=self.pad_fraction,
                           grant_form=self.grant_form,
                           occupancy_peak=self.occupancy_peak,
                           compact_capacity=self.compact_capacity,
                           superstep=self.superstep,
                           escalations=self.escalations,
                           escalation_compiles=self.escalation_compiles)


@dataclass
class ExperimentResult:
    """All grids of one experiment plus flat, seed-averaged records."""

    spec: ExperimentSpec
    grids: list = field(default_factory=list)

    @property
    def wall_s(self) -> float:
        return sum(g.wall_s for g in self.grids)

    @property
    def compile_s(self) -> float:
        return sum(g.compile_s for g in self.grids)

    @property
    def compile_counts(self) -> list:
        return [g.compile_count for g in self.grids]

    @property
    def max_compiles_per_grid(self) -> int:
        return max(self.compile_counts, default=0)

    def rows(self) -> list:
        """Seed-averaged records, one per (grid, fault, rate) — the flat
        table benchmarks print and the CLI serializes.  `wall_s` is the
        grid wall-clock amortized over its rows (per-lane timings don't
        exist in a batched dispatch)."""
        out = []
        for g in self.grids:
            F, R = len(g.fault_labels), len(g.rates)
            dt = g.wall_s / max(F * R, 1)
            for fi in range(F):
                for ri, res in enumerate(g.sweep_result(fi)
                                         .mean_over_seeds()):
                    out.append(dict(
                        scenario=self.spec.name,
                        topology=g.topology.label,
                        topo_kind=g.topology.kind,
                        pattern=g.traffic.label,
                        pattern_name=g.traffic.pattern,
                        pattern_params=dict(g.traffic.params),
                        route_mode=g.routing.route_mode,
                        vc_mode=g.routing.vc_mode,
                        fault=g.fault_labels[fi],
                        fault_frac=g.fault_fracs[fi],
                        offered=g.rates[ri],
                        throughput=res.throughput_per_chip,
                        latency=res.avg_latency,
                        delivered_pkts=res.delivered_pkts,
                        generated_pkts=res.generated_pkts,
                        dropped_pkts=res.dropped_pkts,
                        # exact per-lane max + exact mean (see
                        # SweepResult.mean_over_seeds) and the reaper's
                        # cumulative kill count
                        stranded_pkts=res.stranded_pkts,
                        stranded_mean=res.stranded_mean,
                        reaped_pkts=res.reaped_pkts,
                        avg_hops_by_type=res.avg_hops_by_type,
                        compile_count=g.compile_count,
                        placement=g.placement,
                        pad_fraction=g.pad_fraction,
                        grant_form=g.grant_form,
                        occupancy_peak=res.occupancy_peak,
                        compact_capacity=g.compact_capacity,
                        superstep=g.superstep,
                        escalations=g.escalations,
                        escalation_compiles=g.escalation_compiles,
                        wall_s=dt))
        return out


def _fault_rows(spec: ExperimentSpec, topo: TopologySpec, net: Network,
                vc_mode: str):
    """[F][S] composed fault sets (None = pristine), memoized."""
    rows = []
    for f in spec.axes.faults:
        row = []
        for s in spec.axes.seeds:
            key = (topo, f, vc_mode, s if f.per_seed else None)
            if key not in _FAULT_CACHE:
                _FAULT_CACHE[key] = f.sample(net, vc_mode, s)
            row.append(_FAULT_CACHE[key])
        rows.append(row)
    return rows


def run_experiment(spec: ExperimentSpec, verbose: bool = False
                   ) -> ExperimentResult:
    """Run every grid of `spec`; each grid is one batched-engine dispatch
    (compile_count <= 1 per grid, == 0 on shared-compile reuse).

    Multi-cell specs on multi-device hosts round-robin their cells over
    the devices (async dispatch, materialized after all cells are in
    flight); single-cell specs shard the lane axis over the whole mesh
    inside `run_lanes` instead."""
    import jax

    axes = spec.axes
    rates, seeds = list(axes.rates), list(axes.seeds)
    R, S, F = len(rates), len(seeds), len(axes.faults)
    result = ExperimentResult(spec)
    cell_list = list(cells(spec))
    devs = jax.devices()
    # round-robin cells onto devices only when there are enough cells to
    # occupy them; with fewer cells than devices, sharding each cell's
    # lane axis over the whole mesh uses the machine better than pinning
    # cells to single devices and idling the rest
    round_robin = len(devs) > 1 and len(cell_list) >= len(devs)
    # pass 1: lower every cell's grid and warm the AOT executable cache.
    # All host-blocking compilation happens HERE, before any execution is
    # in flight, so the per-cell wall_s measured below is execution only
    # (a round-robined cell's window never spans another cell's compile).
    plans = []
    for i, cell in enumerate(cell_list):
        key = (cell.topology, cell.routing, cell.traffic,
               axes.warmup, axes.measure, seeds[0])
        sweep = _SWEEP_CACHE.get(key)
        if sweep is None:
            sweep = _SWEEP_CACHE[key] = BatchedSweep(
                cell.net, cell.cfg, cell.pattern)
        frows = _fault_rows(spec, cell.topology, cell.net,
                            cell.routing.vc_mode)
        lanes = [(r, s, frows[fi][si])
                 for fi in range(F)
                 for r in rates
                 for si, s in enumerate(seeds)]
        device = (devs[i % len(devs)]
                  if round_robin
                  and cell.net.num_channels <= rr_max_channels()
                  else None)
        plans.append((cell, sweep, device,
                      sweep.warm_compile(lanes, device=device)))
    # pass 2: dispatch every cell (async; plans are already compiled)
    pending = []
    for cell, sweep, device, plan in plans:
        if verbose:
            where = f" -> {device}" if device is not None else ""
            print(f"[exp:{spec.name}] {cell.topology.label} "
                  f"{cell.routing.label} {cell.traffic.label}: "
                  f"{len(plan.lane_triples)} lanes{where} "
                  f"(compiles={plan.compile_count}) ...",
                  file=sys.stderr, flush=True)
        pending.append((cell, sweep.run_lanes_async(plan=plan)))
    # pass 3: materialize, in dispatch order
    for cell, pend in pending:
        run = pend.finish()
        compile_s, compiles = run.compile_s, run.compile_count
        flat, fsets = run.results, run.fault_sets
        results = [[[flat[(fi * R + ri) * S + si] for si in range(S)]
                    for ri in range(R)] for fi in range(F)]
        fracs = [float(np.mean(
            [0.0 if f is None
             else final_faults(f).frac_links_failed(cell.net)
             for f in fsets[fi * R * S:(fi * R * S) + S]]))
            for fi in range(F)]
        result.grids.append(GridResult(
            topology=cell.topology, routing=cell.routing,
            traffic=cell.traffic, rates=rates, seeds=seeds,
            fault_labels=[f.label for f in axes.faults],
            fault_fracs=fracs, results=results,
            step_impl=run.step_impl, route_cached=run.route_cached,
            compile_count=compiles, wall_s=run.wall_s,
            compile_s=compile_s,
            placement=getattr(run, "placement", "single"),
            pad_fraction=getattr(run, "pad_fraction", 0.0),
            grant_form=getattr(run, "grant_form", "two_pass"),
            occupancy_peak=getattr(run, "occupancy_peak", 0),
            compact_capacity=getattr(run, "compact_capacity", 0),
            superstep=getattr(run, "superstep", 1),
            escalations=getattr(run, "escalations", 0),
            escalation_compiles=getattr(run, "escalation_compiles", 0)))
        if verbose:
            print(f"[exp:{spec.name}]   {cell.topology.label} "
                  f"{cell.routing.label} {cell.traffic.label} done in "
                  f"{run.wall_s:.1f}s (compiles={compiles}, "
                  f"compile_s={compile_s:.1f})",
                  file=sys.stderr, flush=True)
    return result
