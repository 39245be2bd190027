"""Batched load-latency sweeps: `jax.vmap` the engine step over a
(rate x seed) lane axis and run the WHOLE sweep in a single jitted
`lax.scan` — one compilation, one device dispatch per curve, instead of one
sequential `scan` per offered rate.

    sweep = BatchedSweep(net, cfg, pattern)
    grid = sweep.run(rates=[0.2, 0.4, ...], seeds=(0, 1))
    grid.result(i, j)            # SimResult for (rates[i], seeds[j])
    grid.mean_over_seeds()       # list[SimResult], one per rate
    grid.saturation_throughput() # scalar, seed-averaged

Lane (i, j) reproduces `Simulator.run(rates[i])` with `seed=seeds[j]`
bit-for-bit: the per-lane key chain is identical and `vmap` does not change
the per-lane math.

Fault grids: because the fault-dependent data (alive masks + routing
tables, `state.build_lane`) is an explicit step argument, lanes may carry
DIFFERENT fault sets — `run_faults` stacks one lane per (fault set, seed)
and runs a whole failure-rate x seed grid of degraded networks in the same
single compile (see benchmarks/bench_faults.py).

`run_lanes` is the fully general axis: every lane is an independent
(offered rate, seed, fault set) triple, so rate sweeps, seed replication,
and fault grids are all the same one-compile dispatch.  `run` and
`run_faults` are reshaping conveniences over it, and the declarative
experiment runner (`repro.exp.runner`) lowers every `ExperimentSpec` grid
to exactly one `run_lanes` call.

Device parallelism: lanes are independent, so with more than one device
(`REPRO_HOST_DEVICES=N` forces N XLA host devices on CPU; real TPU
backends need no flag) the lane axis is `shard_map`ped across the device
mesh — communication-free SPMD.  Lane counts that do not divide the
device count are padded with GHOST lanes (offered rate 0, dropped before
finalize), so the shard is always dense; each real lane's math is
untouched, keeping sharded runs bit-identical to single-device runs.
Grids too small to amortize the per-cycle shard_map dispatch (fewer than
`REPRO_SHARD_MIN_WORK` lane-cycles, default 4096) skip the lane shard
and run single-device — the chosen placement is recorded in
`SweepResult.placement` (and the perf-benchmark records).

Channel sharding (`REPRO_CHANNEL_SHARDS=K`, fused step only): the mesh
becomes 2-D ``(lanes, shards)`` — each lane's channel-id space is
block-partitioned across K shard devices and the step exchanges
per-channel grant minima / winner records at the phase boundary (see
`engine.fused`).  The big state arrays (`b_pkt`, `s_pkt`) partition on
their channel/terminal axis; everything else stays replicated across
the shard axis.  Ghost channel/terminal padding makes non-dividing
counts dense; `SweepResult.pad_fraction` reports the padded share of
the state so perf records can account for it.

Occupancy compaction (`cfg.step_impl="compact"`): the dispatch layer
owns the capacity LADDER.  A compact dispatch compiles the step at one
rung C (default ceil(N/4); REPRO_COMPACT_CAP pins the start), and
`finish()` checks the run's exact live-row census (`SimStats.occ_peak`)
against it — a breach re-dispatches the WHOLE grid at the next rung up
(`fused.next_rung`), so results handed back are always bit-identical to
the oracle; the rerun count is surfaced as `SweepResult.escalations`.
K-cycle supersteps (REPRO_SUPERSTEP, `superstep()`) unroll K cycles
inside the scan body — per-substep warmup/epoch/window conds keep K > 1
bit-identical to K = 1.

Every dispatch goes through an AOT compile cache, which (a) makes the
compile-vs-run wall-time split exact (`SweepResult.compile_s` /
`wall_s`) and (b) lets `run_lanes_async` return before the result is
materialized, so the experiment runner can round-robin independent grid
cells across devices (see `repro.exp.runner`).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ... import env_int
from ..topology import (FaultSchedule, FaultSet, Network, as_fault_schedule,
                        compose_faults, final_faults)
from ..traffic import as_pattern
from .fused import (fused_pad, grant_form, make_compact_step,
                    make_fused_step, next_rung)
from .state import build_lane, make_state, stack_lanes
from .stats import finalize, zero_stats
from .step import make_step

# Monotone count of batched-scan (re)traces.  `_scan_lanes` bumps it at
# TRACE time (Python side effects run once per compilation, never per
# execution), so a delta across a call counts exactly the compiles that
# call triggered — unlike the private `_cache_size` jit API, which is
# absent on some JAX versions and silently made
# `SweepResult.compile_count` lie as 0.
_TRACE_COUNT = [0]

# AOT executable cache: one compiled batched scan per (step closure,
# cycle budget, lane-shape signature, mesh/device placement).  Explicit
# AOT (`jit(...).lower(...).compile()`) instead of plain `jit` calls
# buys the exact compile-vs-run wall split and executables that can be
# dispatched without blocking (async cell round-robin).
_AOT_CACHE: dict = {}


def compile_counter() -> int:
    """Compilations of the batched scan so far in this process."""
    return _TRACE_COUNT[0]


def clear_aot_cache() -> None:
    """Drop the compiled-executable cache (tests / memory)."""
    _AOT_CACHE.clear()


def host_devices() -> list:
    """The devices the lane axis may spread over (all JAX devices)."""
    return jax.devices()


def shard_min_work() -> int:
    """Minimum (real lanes x cycles) for the automatic lane shard_map to
    pay for its per-cycle dispatch overhead; smaller grids run
    single-device.  Override with REPRO_SHARD_MIN_WORK (0 = always
    shard, as the sharding bit-identity tests do)."""
    return env_int("REPRO_SHARD_MIN_WORK", 4096)


def channel_shards() -> int:
    """Requested channel-shard count K (REPRO_CHANNEL_SHARDS, default 1).
    Only honored by fused-step (`cfg.step_impl="fused"`) dispatches with
    K devices available per lane row."""
    return max(env_int("REPRO_CHANNEL_SHARDS", 1), 1)


def superstep(span: int | None = None) -> int:
    """K-cycle superstep unroll factor (REPRO_SUPERSTEP, default 1).

    With K > 1 the batched scan advances K cycles per scan iteration —
    the K steps are Python-unrolled inside the scan body, so XLA fuses
    across cycle boundaries and the compact step's route-once cache
    (record fields, carried in the state) flows through the unroll with
    no scan-carry round-trip between the K substeps.  Each substep keeps
    its OWN absolute cycle `t` (warmup reset, fault-epoch resolution,
    and window `t_end` masking are all per-substep conds), so unrolling
    cannot skip a warm-fault epoch boundary or the stats reset — the
    result is bit-identical to K = 1 (pinned by tests, proved by the
    analysis capacity pass).

    `span` is the scan length the caller wants to unroll (the cycle
    budget, or a session's window); K falls back to 1 when it does not
    divide `span` (the reshape needs whole supersteps).
    """
    k = max(env_int("REPRO_SUPERSTEP", 1), 1)
    if span is not None and span % k:
        return 1
    return k


def lane_mesh(shards: int = 1) -> Mesh | None:
    """The device mesh for a dispatch: 1-D ``("lanes",)`` over the host
    devices, or 2-D ``("lanes", "shards")`` with `shards` > 1 (each lane
    row owns a K-device channel shard).  None when the process only has
    one device (the common un-forced CPU case)."""
    devs = host_devices()
    nd = len(devs)
    if nd <= 1:
        return None
    if shards > 1:
        if nd % shards:
            raise ValueError(
                f"REPRO_CHANNEL_SHARDS={shards} does not divide the "
                f"{nd} host devices")
        return Mesh(np.array(devs).reshape(nd // shards, shards),
                    ("lanes", "shards"))
    return Mesh(np.array(devs), ("lanes",))


def _key_chain(key, cycles: int):
    """The per-cycle subkeys of one lane, pre-generated outside the main
    scan: `key_{t+1}, sub_t = split(key_t)` — the exact chain the cycle
    loop used to compute inline, hoisted so the simulation scan body no
    longer interleaves a `vmap(split)` with the engine phases."""

    def split(k, _):
        k, sub = jax.random.split(k)
        return k, sub

    _, subs = jax.lax.scan(split, key, None, length=cycles)
    return subs                                            # [cycles, 2]


def _key_chain_seq(key, cycles: int):
    """`_key_chain` plus every intermediate key: `keys_seq[i]` is the lane
    key after i splits (`keys_seq[0] == key`), so a window that runs only
    r <= cycles real cycles can hand `keys_seq[r]` to the next window and
    the whole windowed run replays the uninterrupted subkey chain
    bit-for-bit."""

    def split(k, _):
        k2, sub = jax.random.split(k)
        return k2, (k2, sub)

    _, (ks, subs) = jax.lax.scan(split, key, None, length=cycles)
    return jnp.concatenate([key[None], ks]), subs   # [cycles+1, 2], [cycles, 2]


def _scan_lanes(step, cycles, reset_at, per_lane_faults, K,
                state0, rate_pkt, keys, lanes):
    """Advance B lanes in lockstep; state0/keys/rate_pkt carry axis 0 = B.

    `lanes` is the fault pytree (`build_lane`): lane-stacked ([B, ...],
    `per_lane_faults=True`) when the lanes model different degraded
    networks, or a single shared lane dict broadcast across the batch.

    `K` is the superstep unroll factor (must divide `cycles`; see
    `superstep`): the scan runs cycles/K iterations of K Python-unrolled
    substeps, each with its own absolute `t` — per-substep warmup reset
    and (inside the step) fault-epoch resolution keep the result
    bit-identical to K = 1.
    """
    _TRACE_COUNT[0] += 1  # trace-time side effect == one compilation
    lane_axis = 0 if per_lane_faults else None
    subkeys = jax.vmap(_key_chain, in_axes=(0, None),
                       out_axes=1)(keys, cycles)           # [cycles, B, 2]
    ts = jnp.arange(cycles).reshape(cycles // K, K)
    subkeys = subkeys.reshape((cycles // K, K) + subkeys.shape[1:])

    def body(state, t_subs):
        ts_k, subs_k = t_subs
        for i in range(K):
            t = ts_k[i]
            state, _ = jax.vmap(
                lambda s, k, r, f: step(s, (t, k, r, f)),
                in_axes=(0, 0, 0, lane_axis))(state, subs_k[i], rate_pkt,
                                              lanes)
            st = jax.lax.cond(t == reset_at, zero_stats, lambda s: s,
                              state.stats)
            state = state.replace(stats=st)
        return state, None

    state, _ = jax.lax.scan(body, state0, (ts, subkeys))
    return state


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 7),
                   donate_argnums=(3,))
def run_scan_batched(step, cycles, reset_at, state0, rate_pkt, keys, lanes,
                     per_lane_faults: bool):
    """Single-device batched scan (kept as the stable public entry point;
    `BatchedSweep` itself dispatches through the AOT cache, which adds
    device sharding, supersteps, and the compile/run wall split)."""
    return _scan_lanes(step, cycles, reset_at, per_lane_faults, 1,
                       state0, rate_pkt, keys, lanes)


def _scan_lanes_seq(step, cycles, reset_at, per_lane_faults, K,
                    state0, rate_pkt, keys, lanes):
    """`_scan_lanes` with the lane axis OUTSIDE the cycle scan: one
    `lax.map` over lanes, each lane running its own full-cycle scan.

    Bit-identical to the vmapped form — lanes are independent and the
    per-lane key chain is the same — but each lane's gathers/scatters
    run unbatched, which is how the compact step's occupancy-gather
    pipeline is fastest on CPU: batching the active-set gathers over
    lanes defeats XLA:CPU's contiguous-gather lowering (measured ~25%
    per-lane overhead at fig11 scale), and a single host core gains
    nothing from the lockstep form anyway.  Selected by the dispatch
    planner for single-device compact runs only; meshes keep the
    lockstep form (shard_map partitions the lane axis)."""
    _TRACE_COUNT[0] += 1  # trace-time side effect == one compilation
    subkeys = jax.vmap(_key_chain, in_axes=(0, None))(keys, cycles)
    ts = jnp.arange(cycles).reshape(cycles // K, K)

    def one_lane(st0_subs_rate_fl):
        st0, subs, rate, fl = st0_subs_rate_fl
        subs_r = subs.reshape((cycles // K, K) + subs.shape[1:])

        def body(state, t_subs):
            ts_k, subs_k = t_subs
            for i in range(K):
                t = ts_k[i]
                state, _ = step(state, (t, subs_k[i], rate, fl))
                st = jax.lax.cond(t == reset_at, zero_stats,
                                  lambda s: s, state.stats)
                state = state.replace(stats=st)
            return state, None

        return jax.lax.scan(body, st0, (ts, subs_r))[0]

    if per_lane_faults:
        return jax.lax.map(one_lane, (state0, subkeys, rate_pkt, lanes))
    return jax.lax.map(
        lambda args: one_lane(args + (lanes,)),
        (state0, subkeys, rate_pkt))


def _make_dispatch_fn(step, cycles, reset_at, per_lane_faults, mesh,
                      state_spec=None, K=1):
    """The jittable whole-sweep function, `shard_map`ped over the lane
    axis when a mesh is given (lanes are independent: no collectives, so
    partitioning axis 0 is communication-free SPMD).  `state_spec` is a
    per-leaf PartitionSpec tree for the state (the 2-D channel-sharded
    mesh partitions `b_pkt`/`s_pkt` on their channel axis and replicates
    the rest across the shard axis); the default partitions every leaf
    on the lane axis only."""
    scan_form = (_scan_lanes_seq
                 if mesh is None and getattr(step, "compact_capacity", 0)
                 else _scan_lanes)
    f = functools.partial(scan_form, step, cycles, reset_at,
                          per_lane_faults, K)
    if mesh is not None:
        lane_spec = PartitionSpec("lanes")
        if state_spec is None:
            state_spec = lane_spec
        data_spec = lane_spec if per_lane_faults else PartitionSpec()
        f = shard_map(f, mesh=mesh,
                       in_specs=(state_spec, lane_spec, lane_spec,
                                 data_spec),
                       out_specs=state_spec, check_vma=False)
    return jax.jit(f, donate_argnums=(0,))


def _scan_window(step, window, reset_at, per_lane_faults, K,
                 state0, keys, t0, t_end, rate_pkt, lanes):
    """Advance B lanes exactly `window` scan iterations starting at
    absolute cycle `t0`, masking iterations at or past `t_end` to a
    no-op (`lax.cond` keeps the carried state untouched), and return the
    advanced `(state, keys)` pair.

    The fixed iteration count is what makes windowed execution compile
    ONCE per lane signature: every window of a run — including the final
    partial one — dispatches the same executable with different traced
    `t0`/`t_end` scalars.  Keys advance only for the real cycles
    (`keys_seq` gather), so chaining windows replays the exact subkey
    chain of the one-shot `_scan_lanes` run and the windowed result is
    bit-identical to the uninterrupted one.

    `K` supersteps the window scan like `_scan_lanes` (must divide
    `window`); the `t < t_end` no-op mask stays PER SUBSTEP, so a
    partial final window masks exactly the same cycles as K = 1.
    """
    _TRACE_COUNT[0] += 1  # trace-time side effect == one compilation
    lane_axis = 0 if per_lane_faults else None
    keys_seq, subkeys = jax.vmap(_key_chain_seq, in_axes=(0, None),
                                 out_axes=(1, 1))(keys, window)
    # keys_seq [window+1, B, 2], subkeys [window, B, 2]
    ts = (t0 + jnp.arange(window)).reshape(window // K, K)
    subs_r = subkeys.reshape((window // K, K) + subkeys.shape[1:])

    def body(state, t_subs):
        ts_k, subs_k = t_subs
        for i in range(K):
            t, subs = ts_k[i], subs_k[i]

            def advance(st):
                st, _ = jax.vmap(
                    lambda s, k, r, f: step(s, (t, k, r, f)),
                    in_axes=(0, 0, 0, lane_axis))(st, subs, rate_pkt,
                                                  lanes)
                stats = jax.lax.cond(t == reset_at, zero_stats,
                                     lambda s: s, st.stats)
                return st.replace(stats=stats)

            state = jax.lax.cond(t < t_end, advance, lambda st: st, state)
        return state, None

    state, _ = jax.lax.scan(body, state0, (ts, subs_r))
    real = jnp.clip(t_end - t0, 0, window)
    return state, keys_seq[real]


def _make_window_fn(step, window, reset_at, per_lane_faults, mesh, K=1):
    """The jittable one-window function, `shard_map`ped over the lane
    axis when a mesh is given (mirrors `_make_dispatch_fn`; the traced
    `t0`/`t_end` scalars replicate across devices).  State and keys are
    donated — each window consumes the previous window's buffers."""
    f = functools.partial(_scan_window, step, window, reset_at,
                          per_lane_faults, K)
    if mesh is not None:
        lane_spec = PartitionSpec("lanes")
        scal_spec = PartitionSpec()
        data_spec = lane_spec if per_lane_faults else scal_spec
        f = shard_map(f, mesh=mesh,
                       in_specs=(lane_spec, lane_spec, scal_spec,
                                 scal_spec, lane_spec, data_spec),
                       out_specs=(lane_spec, lane_spec), check_vma=False)
    return jax.jit(f, donate_argnums=(0, 1))


def _sig(tree) -> tuple:
    """Hashable shape/dtype signature of a pytree (AOT cache key part)."""
    return (jax.tree.structure(tree),
            tuple((l.shape, str(l.dtype)) for l in jax.tree.leaves(tree)))


def offered_to_rate_pkt(offered_per_chip: float, cfg,
                        terms_per_chip: float) -> float:
    """Offered flits/cycle/chip -> per-terminal packet-generation rate.

    Shared by the facade `Simulator.run` and `BatchedSweep`; raises when the
    offered load would need more than one packet per terminal per cycle.
    """
    rate = offered_per_chip / cfg.pkt_len / terms_per_chip
    if rate > 1.0 + 1e-9:
        raise ValueError(
            f"offered {offered_per_chip}/chip needs per-terminal packet "
            f"rate {rate:.2f} > 1")
    return rate


class LaneRun(NamedTuple):
    """The outcome of one `run_lanes` dispatch."""

    results: list          # one SimResult per lane, in lane order
    wall_s: float          # execution wall time (compile excluded)
    compile_s: float       # trace + compile wall time (0.0 on cache hit)
    compile_count: int     # jit compilations this dispatch triggered
    fault_sets: list       # composed per-lane fault states (None=pristine)
    placement: str = "single"   # "single" | "lanes:L" | "lanes:L,shards:K"
    pad_fraction: float = 0.0   # ghost share of the dispatched state
    grant_form: str = "two_pass"   # "combined" | "two_pass" (see fused.py)
    occupancy_peak: int = 0     # max live request rows over the real lanes
    compact_capacity: int = 0   # compact step's final ladder rung (0=dense)
    superstep: int = 1          # K-cycle unroll the dispatch compiled
    escalations: int = 0        # capacity-ladder reruns this run needed
    # compiles spent on ABANDONED (breached) rungs: kept out of
    # `compile_count` so the one-compile-per-grid accounting stays exact
    # per executable — each ladder rung is its own executable
    escalation_compiles: int = 0


@dataclass
class SweepResult:
    """SimResults on the (rate x seed) grid, plus curve-level reductions.

    For fault sweeps (`BatchedSweep.run_faults`) the row axis is the fault
    grid instead of the rate grid: `rates[i]` repeats the common offered
    load and `fault_fracs[i]` labels row i with its failed-link fraction.

    `wall_s` is EXECUTION time only; trace + compile time is `compile_s`
    (0.0 when the dispatch was an executable-cache hit), so first-call
    timings no longer conflate the two.
    """

    rates: list[float]
    seeds: list[int]
    results: list[list]        # [num_rates][num_seeds] of SimResult
    compile_count: int = 0     # jit compilations this sweep triggered
    wall_s: float = 0.0
    compile_s: float = 0.0
    fault_fracs: list | None = None   # per-row failed-link fraction (faults)
    placement: str = "single"  # device placement the dispatch chose
    pad_fraction: float = 0.0  # ghost (lane + channel pad) state share
    # grant arbitration form the dispatch compiled: "combined" (the fused
    # step's single packed segment-min) or "two_pass" (the age-then-
    # priority oracle form — also what the fused step falls back to when
    # the packed key would overflow int32; `fused.grant_form` decides,
    # and the static spec pass reports/warns per scenario)
    grant_form: str = "two_pass"
    # occupancy / compaction telemetry (see engine.fused.make_compact_step):
    # peak live request rows over the whole grid, the compact step's FINAL
    # capacity rung (0 for the dense steps), the K-cycle superstep the
    # dispatch compiled, and how many capacity-ladder reruns were needed
    occupancy_peak: int = 0
    compact_capacity: int = 0
    superstep: int = 1
    escalations: int = 0
    # compiles the abandoned rungs cost (separate from `compile_count`:
    # every rung is its own executable, so the per-grid count stays 1)
    escalation_compiles: int = 0

    def result(self, rate_idx: int, seed_idx: int = 0):
        return self.results[rate_idx][seed_idx]

    def flat(self):
        return [r for row in self.results for r in row]

    def mean_over_seeds(self) -> list:
        """One seed-averaged SimResult per rate.

        Rates/latencies are means over the seed lanes; packet counters are
        floor-averaged (NOT summed) so they stay comparable to a single
        `Simulator.run`.  Reliability gauges are different: `stranded_pkts`
        reports the exact per-lane MAX (a floor-averaged mean hid single
        stranded wafers — 1 stranded packet across 8 seeds floored to 0),
        with the exact mean in the float `stranded_mean`; `occupancy_peak`
        is likewise the max."""
        from ..simulator import SimResult
        out = []
        for row in self.results:
            n = len(row)
            hops = {k: sum(r.hops_by_type[k] for r in row) // n
                    for k in row[0].hops_by_type}
            avg_hops = {k: float(np.mean([r.avg_hops_by_type[k] for r in row]))
                        for k in row[0].avg_hops_by_type}
            out.append(SimResult(
                offered_per_chip=row[0].offered_per_chip,
                throughput_per_chip=float(
                    np.mean([r.throughput_per_chip for r in row])),
                avg_latency=float(np.mean([r.avg_latency for r in row])),
                delivered_pkts=sum(r.delivered_pkts for r in row) // n,
                generated_pkts=sum(r.generated_pkts for r in row) // n,
                dropped_pkts=sum(r.dropped_pkts for r in row) // n,
                hops_by_type=hops, avg_hops_by_type=avg_hops,
                stranded_pkts=max(r.stranded_pkts for r in row),
                stranded_mean=float(
                    np.mean([r.stranded_pkts for r in row])),
                reaped_pkts=sum(r.reaped_pkts for r in row) // n,
                occupancy_peak=max(r.occupancy_peak for r in row)))
        return out

    def saturation_throughput(self) -> float:
        """Max seed-averaged accepted throughput over the sweep."""
        return max(r.throughput_per_chip for r in self.mean_over_seeds())


class _LanePlan:
    """A prepared, placed, and compiled — but not yet executed — lane
    dispatch (`BatchedSweep.warm_compile`).  Single-use: execution
    donates the plan's initial state buffer.  `compile_s` and
    `compile_count` are zero when the executable came from the AOT
    cache."""

    __slots__ = ("lane_triples", "fault_sets", "args", "compiled",
                 "compile_s", "compile_count", "placement",
                 "pad_fraction", "grant_form", "capacity", "rows",
                 "superstep", "device", "used")

    def __init__(self, lane_triples, fault_sets, args, compiled,
                 compile_s, compile_count, placement, pad_fraction,
                 grant_form, capacity=0, rows=0, superstep=1,
                 device=None):
        self.lane_triples = lane_triples
        self.fault_sets = fault_sets
        self.args = args
        self.compiled = compiled
        self.compile_s = compile_s
        self.compile_count = compile_count
        self.placement = placement
        self.pad_fraction = pad_fraction
        self.grant_form = grant_form
        self.capacity = capacity      # compact rung this plan compiled
        self.rows = rows              # N, the dense request-row count
        self.superstep = superstep
        self.device = device          # pinned device (escalation reruns)
        self.used = False


class _PendingLanes:
    """A dispatched-but-unmaterialized `run_lanes` call.

    The compiled executable has been enqueued (JAX dispatch is async);
    `finish()` blocks on the device result and builds the per-lane
    `SimResult`s.  `wall_s` therefore measures dispatch -> materialized,
    which for overlapped (round-robined) cells includes time the device
    spent interleaved with other work.
    """

    def __init__(self, sweep, stats, num_lanes, lane_triples, fault_sets,
                 compile_s, compile_count, t0, placement, pad_fraction,
                 grant_form, capacity=0, rows=0, superstep=1,
                 device=None):
        self._sweep, self._stats = sweep, stats
        self._B, self._lanes = num_lanes, lane_triples
        self._fsets = fault_sets
        self._compile_s, self._compiles = compile_s, compile_count
        self._t0 = t0
        self._placement, self._pad_frac = placement, pad_fraction
        self._grant_form = grant_form
        self._capacity, self._rows = capacity, rows
        self._superstep = superstep
        self._device = device

    def finish(self) -> LaneRun:
        stats = jax.tree.map(np.asarray, self._stats)      # blocks
        wall = time.perf_counter() - self._t0
        cfg = self._sweep.cfg
        occ = int(np.max(stats.occ_peak[:self._B]))
        if self._capacity and occ > self._capacity:
            # capacity breach: the live set outgrew this rung, so every
            # cycle after the crossing arbitrated over a TRUNCATED active
            # set — nothing from this run can be trusted (or reused).
            # Re-dispatch the WHOLE grid at the next ladder rung; the
            # rerun is deterministic (same lanes, same keys), so the
            # escalated result is bit-identical to the oracle.  `occ` is
            # exact (the census is computed densely, independent of C),
            # and the top rung C = N cannot breach, so the walk
            # terminates.
            rung = next_rung(self._rows, occ)
            self._sweep._capacity_floor = max(
                self._sweep._capacity_floor, rung)
            redo = self._sweep.run_lanes_async(
                self._lanes, device=self._device, capacity=rung).finish()
            return redo._replace(
                wall_s=redo.wall_s + wall,
                compile_s=redo.compile_s + self._compile_s,
                escalations=redo.escalations + 1,
                escalation_compiles=(redo.escalation_compiles
                                     + self._compiles))
        pick = lambda i: jax.tree.map(lambda x: x[i], stats)
        results = [finalize(pick(i), cfg, self._lanes[i][0],
                            self._sweep._chips(self._fsets[i]))
                   for i in range(self._B)]     # ghost pad lanes excluded
        return LaneRun(results, wall, self._compile_s, self._compiles,
                       self._fsets, self._placement, self._pad_frac,
                       self._grant_form, occ, self._capacity,
                       self._superstep)


class LaneSession:
    """A paused, resumable lane dispatch advanced window-by-window.

    Created by `BatchedSweep.start_lanes`.  Unlike `run_lanes` — which
    scans the whole cycle budget in one dispatch — a session holds the
    live `SimState` (and the per-lane PRNG keys) between fixed-length
    window dispatches, so a long-lived caller (`repro.exp.serve`) can
    stream incremental stats after every window, checkpoint the state
    mid-run, and interleave many independent sessions on one process.
    Chained windows replay the one-shot run's per-cycle subkey chain
    exactly, so `finish()` is bit-identical to `run_lanes` on the same
    lane triples (pinned by tests/test_serve.py).

    `export()` snapshots the session's dynamic state to host numpy
    arrays; `BatchedSweep.start_lanes(..., restore=exported)` resumes a
    fresh session from a snapshot — resumed runs reproduce the
    uninterrupted run bit-for-bit because the state arrays, the lane
    keys, and the absolute cycle count are the entire dynamic state.
    """

    __slots__ = ("sweep", "lane_triples", "fault_sets", "window", "total",
                 "cycle", "state", "keys", "compiled", "placement",
                 "pad_fraction", "grant_form", "compile_s", "compile_count",
                 "num_lanes", "capacity", "superstep", "_rate_pkt_dev",
                 "_lane_data")

    def __init__(self, sweep, lane_triples, fault_sets, window, total,
                 cycle, state, keys, compiled, rate_pkt, lane_data,
                 placement, pad_fraction, grant_form, compile_s,
                 compile_count, capacity=0, superstep=1):
        self.sweep = sweep
        self.lane_triples = lane_triples
        self.fault_sets = fault_sets
        self.window = window
        self.total = total
        self.cycle = cycle
        self.state = state
        self.keys = keys
        self.compiled = compiled
        self._rate_pkt_dev = rate_pkt
        self._lane_data = lane_data
        self.placement = placement
        self.pad_fraction = pad_fraction
        self.grant_form = grant_form
        self.compile_s = compile_s
        self.compile_count = compile_count
        self.capacity = capacity      # compact rung (0 for dense steps)
        self.superstep = superstep
        self.num_lanes = len(lane_triples)

    def done(self) -> bool:
        return self.cycle >= self.total

    def advance(self) -> int:
        """Run one window (`window` cycles, clipped at the total budget);
        returns the new absolute cycle count."""
        if self.done():
            return self.cycle
        t0 = jnp.asarray(self.cycle, jnp.int32)
        t_end = jnp.asarray(self.total, jnp.int32)
        self.state, self.keys = self.compiled(
            self.state, self.keys, t0, t_end, self._rate_pkt_dev,
            self._lane_data)
        self.cycle = min(self.cycle + self.window, self.total)
        return self.cycle

    def stats_host(self):
        """The current per-lane `SimStats` counters as host numpy arrays
        (leading axis = padded lane count; real lanes are the first
        `num_lanes` rows).  Blocks on any in-flight window."""
        return jax.tree.map(np.asarray, self.state.stats)

    def lane_stats(self, i: int):
        """Real lane i's current counters (host)."""
        st = self.stats_host()
        return jax.tree.map(lambda x: x[i], st)

    def export(self) -> dict:
        """Snapshot the session's full dynamic state to host arrays:
        `{"state": SimState-of-numpy, "keys": [Bp, 2] uint32,
        "cycle": int}` — everything `restore=` needs for a bit-identical
        resume (the static side is rebuilt from the lane triples)."""
        return dict(state=jax.tree.map(np.asarray, self.state),
                    keys=np.asarray(self.keys),
                    cycle=int(self.cycle))

    def finish(self) -> LaneRun:
        """Per-lane `SimResult`s once the cycle budget is exhausted —
        the same shape of answer `run_lanes` returns (wall_s is not
        tracked per-window; reported as 0.0)."""
        if not self.done():
            raise ValueError(
                f"session at cycle {self.cycle}/{self.total}: advance() "
                f"to the full budget before finish()")
        stats = self.stats_host()
        cfg = self.sweep.cfg
        occ = int(np.max(stats.occ_peak[:self.num_lanes]))
        if self.capacity and occ > self.capacity:
            # a windowed session cannot escalate (its exported snapshots
            # and streamed stats already reflect the truncated active
            # set), so a breach is a hard error with the fix spelled out
            raise RuntimeError(
                f"compact capacity {self.capacity} overflowed: the live "
                f"set peaked at {occ} rows — windowed sessions cannot "
                f"re-dispatch at a larger ladder rung mid-run; rerun "
                f"with REPRO_COMPACT_CAP>={occ} (or step_impl='fused')")
        pick = lambda i: jax.tree.map(lambda x: x[i], stats)
        results = [finalize(pick(i), cfg, self.lane_triples[i][0],
                            self.sweep._chips(self.fault_sets[i]))
                   for i in range(self.num_lanes)]
        return LaneRun(results, 0.0, self.compile_s, self.compile_count,
                       self.fault_sets, self.placement, self.pad_fraction,
                       self.grant_form, occ, self.capacity,
                       self.superstep)


class BatchedSweep:
    """Compile-once sweep runner over a (rate x seed) lane grid.

    The step closure is shared with `Simulator` (same phases, same consts);
    `route_fn` and the traffic pattern only ever see per-lane data, so the
    whole cycle is batch-pure and legal to `vmap`.  `faults` degrades every
    lane with one fault set; `run_faults` runs a grid of different fault
    sets in one compile.
    """

    def __init__(self, net: Network, cfg, pattern, inject_mask=None,
                 step=None, consts=None, faults: FaultSet | None = None,
                 lane=None):
        self.net, self.cfg = net, cfg
        pattern = as_pattern(pattern, inject_mask)
        if step is None:
            step, consts = make_step(net, cfg, pattern)
        self.step, self.consts = step, consts
        self.NV = consts["NV"]
        self._pattern = pattern
        self._sharded_steps: dict[int, object] = {}
        self._compact_steps: dict[int, object] = {}
        self._capacity_floor = 0    # highest escalated rung seen so far
        self.faults = faults
        self.lane0 = build_lane(net, cfg, faults) if lane is None else lane
        self.terms_per_chip = net.num_terminals / net.num_chips
        self._inj_mask = (np.ones(net.num_terminals, dtype=bool)
                          if pattern.inject_mask is None
                          else np.asarray(pattern.inject_mask).astype(bool))

    def _rate_pkt(self, offered_per_chip: float) -> float:
        return offered_to_rate_pkt(offered_per_chip, self.cfg,
                                   self.terms_per_chip)

    def _sharded_step(self, K: int):
        """The K-way channel-sharded fused step (memoized: one build per
        shard count, so repeat dispatches hit the AOT cache)."""
        step = self._sharded_steps.get(K)
        if step is None:
            step, _ = make_fused_step(self.net, self.cfg, self._pattern,
                                      shards=K)
            self._sharded_steps[K] = step
        return step

    def _compact_step(self, C: int):
        """The capacity-C compact step (memoized per ladder rung: the
        base `self.step` for its own rung, a fresh build otherwise — so
        an escalation's first rerun compiles once and later reruns at
        the same rung hit the AOT cache)."""
        step = self._compact_steps.get(C)
        if step is None:
            if getattr(self.step, "compact_capacity", None) == C:
                step = self.step
            else:
                step, _ = make_compact_step(self.net, self.cfg,
                                            self._pattern, capacity=C)
            self._compact_steps[C] = step
        return step

    def _chips(self, faults) -> float:
        """Accepted-throughput divisor: chips weighted by the fraction of
        terminals that actually inject (mask AND alive).  A schedule
        reports its FINAL epoch — the steady-state degraded network."""
        faults = final_faults(faults)
        alive = (self._inj_mask if faults is None
                 else self._inj_mask & faults.term_alive(self.net))
        return self.net.num_chips * alive.sum() / self.net.num_terminals

    def _plan(self, lanes, device=None, capacity=None) -> "_LanePlan":
        """Prepare, place, and compile (cache-aware) ONE batched scan
        over the (ghost-padded) lane axis — without executing it.

        `device=None` shards lanes over the full device mesh (no-op with
        one device); an explicit `device` pins the whole dispatch there
        (the runner's cell round-robin).  `capacity` overrides the
        compact step's ladder rung (the escalation rerun path; ignored
        for the dense steps).  The returned plan is single-use:
        executing it donates its initial state buffer.
        """
        lane_triples, lane_rates, lane_keys, lane_data, per_lane_faults, \
            fsets = self._prepare_lanes(lanes)
        cfg = self.cfg
        B = int(lane_rates.shape[0])
        cycles = cfg.warmup + cfg.measure
        impl = getattr(cfg, "step_impl", "jnp")
        fused = impl == "fused"
        compact = impl == "compact"
        K = channel_shards() if (fused and device is None) else 1
        mesh = lane_mesh(K) if K > 1 else None
        if mesh is None:
            K = 1       # < K devices: channel sharding can't apply
            small = B * cycles < shard_min_work()
            if device is None and B > 1 and not small:
                mesh = lane_mesh()
        if K > 1:
            step = self._sharded_step(K)
        elif compact and capacity is not None:
            step = self._compact_step(int(capacity))
        elif compact and self._capacity_floor:
            # warm start: an earlier dispatch of this sweep escalated, so
            # later dispatches start straight at the proven rung instead
            # of re-breaching the default one every run
            step = self._compact_step(self._capacity_floor)
        else:
            step = self.step
        # the arbitration form this dispatch compiles: the oracle step IS
        # the two-pass form; the fused/compact steps pick per
        # `fused.grant_form`
        gform = (grant_form(self.net, cfg, K) if fused or compact
                 else "two_pass")
        cap = getattr(step, "compact_capacity", 0)
        kss = superstep(cycles)
        ch_pad, term_pad = fused_pad(self.net, K) if K > 1 else (0, 0)
        nd = int(mesh.shape["lanes"]) if mesh is not None else 1
        pad = (-B) % nd
        if mesh is None:
            placement = "single"
        elif K > 1:
            placement = f"lanes:{nd},shards:{K}"
        else:
            placement = f"lanes:{nd}"
        E = self.net.num_channels
        pad_fraction = 1.0 - (B * E) / ((B + pad) * (E + ch_pad))
        if pad:
            # ghost lanes: offered rate 0 (inject generates nothing), any
            # valid key/fault data; their stats are never read back
            lane_rates = jnp.concatenate(
                [lane_rates, jnp.zeros((pad,), lane_rates.dtype)])
            lane_keys = jnp.concatenate(
                [lane_keys,
                 jnp.broadcast_to(lane_keys[:1],
                                  (pad,) + lane_keys.shape[1:])])
            if per_lane_faults:
                lane_data = jax.tree.map(
                    lambda x: jnp.concatenate(
                        [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])]),
                    lane_data)
        state0 = make_state(self.net, cfg, self.NV, batch=(B + pad,),
                            ch_pad=ch_pad, term_pad=term_pad)
        state_spec = None
        if K > 1:
            # 2-D placement: the big per-channel/per-terminal arrays
            # partition on their second axis, the rest replicates
            # across the shard axis
            state_spec = jax.tree.map(lambda _: PartitionSpec("lanes"),
                                      state0)
            state_spec = state_spec.replace(
                b_pkt=PartitionSpec("lanes", "shards"),
                s_pkt=PartitionSpec("lanes", "shards"))
        if mesh is not None:
            lane_sh = NamedSharding(mesh, PartitionSpec("lanes"))
            repl_sh = NamedSharding(mesh, PartitionSpec())
            if state_spec is None:
                state0 = jax.device_put(state0, lane_sh)
            else:
                # PartitionSpec subclasses tuple, so the spec tree can't
                # be tree-mapped over — build a NamedSharding-leaf tree
                sh_tree = jax.tree.map(lambda _: lane_sh, state0)
                sh_tree = sh_tree.replace(
                    b_pkt=NamedSharding(
                        mesh, PartitionSpec("lanes", "shards")),
                    s_pkt=NamedSharding(
                        mesh, PartitionSpec("lanes", "shards")))
                state0 = jax.tree.map(jax.device_put, state0, sh_tree)
            lane_rates = jax.device_put(lane_rates, lane_sh)
            lane_keys = jax.device_put(lane_keys, lane_sh)
            lane_data = jax.device_put(
                lane_data, lane_sh if per_lane_faults else repl_sh)
        elif device is not None:
            state0, lane_rates, lane_keys, lane_data = jax.device_put(
                (state0, lane_rates, lane_keys, lane_data), device)
        cache_key = (step, cycles, cfg.warmup, per_lane_faults, mesh,
                     device, kss, _sig((state0, lane_rates, lane_keys,
                                        lane_data)))
        compiled = _AOT_CACHE.get(cache_key)
        compile_s = 0.0
        compiles = 0
        if compiled is None:
            fn = _make_dispatch_fn(step, cycles, cfg.warmup,
                                   per_lane_faults, mesh, state_spec, kss)
            before = _TRACE_COUNT[0]
            t0 = time.perf_counter()
            compiled = fn.lower(state0, lane_rates, lane_keys,
                                lane_data).compile()
            compile_s = time.perf_counter() - t0
            compiles = _TRACE_COUNT[0] - before
            _AOT_CACHE[cache_key] = compiled
        return _LanePlan(lane_triples, fsets,
                         (state0, lane_rates, lane_keys, lane_data),
                         compiled, compile_s, compiles, placement,
                         pad_fraction, gform, cap,
                         getattr(step, "compact_rows", 0), kss, device)

    def _prepare_lanes(self, lanes, force_stack: bool = False,
                       epochs: int | None = None):
        """Compose/sample per-lane fault data; returns the dense lane
        arrays plus the composed fault states.  `force_stack` always
        stacks the lane axis even when every lane shares one fault state
        — window sessions use it so a bucket's dispatch signature never
        depends on which tenants' lanes happened to be packed together.
        `epochs` forces the schedule (epoch-stacked) lane form padded to
        at least that many epochs, even for an all-cold lane list, so
        every pack of a warm bucket keeps one dispatch signature."""
        cfg = self.cfg
        lanes = list(lanes)
        if not lanes:
            raise ValueError("run_lanes needs >= 1 lane")
        base = self.faults
        fsets = [compose_faults(base, f) for _, _, f in lanes]
        if (epochs is not None
                or any(isinstance(f, FaultSchedule) for f in fsets)):
            fsets = [as_fault_schedule(f) for f in fsets]
        lane_rates = jnp.asarray([self._rate_pkt(r) for r, _, _ in lanes],
                                 dtype=jnp.float32)
        lane_keys = jnp.stack(
            [jax.random.PRNGKey(int(s)) for _, s, _ in lanes])
        if len(set(fsets)) == 1 and not force_stack:
            lane_data = (self.lane0 if fsets[0] == base
                         else build_lane(self.net, cfg, fsets[0]))
            per_lane = False
        else:
            # FaultSet is frozen/hashable: build each distinct lane once
            # even when many lanes share one fault set
            memo = {}
            for f in fsets:
                if f not in memo:
                    memo[f] = build_lane(self.net, cfg, f)
            lane_data = stack_lanes([memo[f] for f in fsets],
                                    epochs=epochs)
            per_lane = True
        return lanes, lane_rates, lane_keys, lane_data, per_lane, fsets

    def warm_compile(self, lanes, device=None) -> "_LanePlan":
        """Prepare and compile the lane grid without executing it.

        The experiment runner warms EVERY cell before dispatching any
        execution, so a round-robined cell's wall_s window never
        contains another cell's host-blocking compilation; the returned
        plan is then handed back to `run_lanes_async(plan=...)`, reusing
        the prepared lane arrays (no second fault-table build)."""
        return self._plan(lanes, device=device)

    def start_lanes(self, lanes, *, window: int, device=None,
                    pad_to: int | None = None, force_stack: bool = False,
                    epochs: int | None = None,
                    restore: dict | None = None) -> LaneSession:
        """Open a window-sliced `LaneSession` over `lanes` instead of
        scanning the whole cycle budget at once.

        `window` is the fixed per-dispatch cycle count: every window —
        including the final partial one — runs the SAME compiled
        executable (cycles past the budget are masked no-ops), so a
        session costs at most one compile per lane signature no matter
        how its total budget divides.  `pad_to` ghost-pads the lane axis
        up to a fixed batch size (rate-0 lanes, dropped from results) so
        heterogeneous packings of the same signature share one
        executable; `force_stack` pins the per-lane fault axis stacked
        and `epochs` pins the schedule form padded to a fixed epoch
        count, both for the same reason.  `restore` resumes from a prior
        session's
        `export()` snapshot (same lane triples required) — the resumed
        run is bit-identical to the uninterrupted one.

        Sessions ignore `REPRO_CHANNEL_SHARDS` (the 2-D fused-step mesh
        is a whole-run dispatch); the lane axis still `shard_map`s over
        multi-device hosts when the padded batch divides the mesh.
        """
        if window < 1:
            raise ValueError(f"window must be >= 1 cycles, got {window}")
        lane_triples, lane_rates, lane_keys, lane_data, per_lane_faults, \
            fsets = self._prepare_lanes(lanes, force_stack=force_stack,
                                        epochs=epochs)
        cfg = self.cfg
        B = int(lane_rates.shape[0])
        if pad_to is not None and pad_to < B:
            raise ValueError(f"pad_to={pad_to} < {B} lanes")
        target = max(B, pad_to or 0)
        cycles = cfg.warmup + cfg.measure
        mesh = None
        if device is None and target > 1 \
                and target * cycles >= shard_min_work():
            mesh = lane_mesh()
        nd = int(mesh.shape["lanes"]) if mesh is not None else 1
        Bp = target + (-target) % nd
        pad = Bp - B
        placement = "single" if mesh is None else f"lanes:{nd}"
        impl = getattr(cfg, "step_impl", "jnp")
        gform = (grant_form(self.net, cfg, 1) if impl in ("fused", "compact")
                 else "two_pass")
        step = self.step
        if impl == "compact" and self._capacity_floor:
            # sessions cannot escalate mid-run (finish() raises on a
            # breach), so start at the highest rung this sweep has ever
            # had to escalate to
            step = self._compact_step(self._capacity_floor)
        cap = getattr(step, "compact_capacity", 0)
        kss = superstep(window)
        if pad:
            lane_rates = jnp.concatenate(
                [lane_rates, jnp.zeros((pad,), lane_rates.dtype)])
            lane_keys = jnp.concatenate(
                [lane_keys,
                 jnp.broadcast_to(lane_keys[:1],
                                  (pad,) + lane_keys.shape[1:])])
            if per_lane_faults:
                lane_data = jax.tree.map(
                    lambda x: jnp.concatenate(
                        [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])]),
                    lane_data)
        state0 = make_state(self.net, cfg, self.NV, batch=(Bp,))
        cycle = 0
        if restore is not None:
            want = _sig((state0, lane_keys))
            got = _sig((restore["state"], restore["keys"]))
            if want != got:
                raise ValueError(
                    "restore snapshot does not match this session's lane "
                    "signature (different lane count, padding, or config)")
            state0 = jax.tree.map(jnp.asarray, restore["state"])
            lane_keys = jnp.asarray(restore["keys"])
            cycle = int(restore["cycle"])
            if not 0 <= cycle <= cycles:
                raise ValueError(
                    f"restore cycle {cycle} outside [0, {cycles}]")
        t0 = jnp.asarray(cycle, jnp.int32)
        t_end = jnp.asarray(cycles, jnp.int32)
        if mesh is not None:
            lane_sh = NamedSharding(mesh, PartitionSpec("lanes"))
            repl_sh = NamedSharding(mesh, PartitionSpec())
            state0 = jax.device_put(state0, lane_sh)
            lane_rates = jax.device_put(lane_rates, lane_sh)
            lane_keys = jax.device_put(lane_keys, lane_sh)
            lane_data = jax.device_put(
                lane_data, lane_sh if per_lane_faults else repl_sh)
        elif device is not None:
            state0, lane_rates, lane_keys, lane_data = jax.device_put(
                (state0, lane_rates, lane_keys, lane_data), device)
        cache_key = ("window", step, window, cfg.warmup,
                     per_lane_faults, mesh, device, kss,
                     _sig((state0, lane_keys, t0, t_end, lane_rates,
                           lane_data)))
        compiled = _AOT_CACHE.get(cache_key)
        compile_s = 0.0
        compiles = 0
        if compiled is None:
            fn = _make_window_fn(step, window, cfg.warmup,
                                 per_lane_faults, mesh, kss)
            before = _TRACE_COUNT[0]
            t_c = time.perf_counter()
            compiled = fn.lower(state0, lane_keys, t0, t_end, lane_rates,
                                lane_data).compile()
            compile_s = time.perf_counter() - t_c
            compiles = _TRACE_COUNT[0] - before
            _AOT_CACHE[cache_key] = compiled
        return LaneSession(self, lane_triples, fsets, window, cycles,
                           cycle, state0, lane_keys, compiled, lane_rates,
                           lane_data, placement, 1.0 - B / Bp, gform,
                           compile_s, compiles, cap, kss)

    def run_lanes_async(self, lanes=None, device=None,
                        plan: "_LanePlan | None" = None,
                        capacity=None) -> _PendingLanes:
        """Dispatch the lane grid without blocking on the result.

        Compilation (cache-miss only) still blocks the host, but the
        execution is enqueued asynchronously — the caller can dispatch
        further independent grids (e.g. on other devices) and `finish()`
        them in order.  `device` pins the whole grid to one device
        instead of sharding it over the mesh; `plan` executes an
        already-warm `warm_compile` plan instead of preparing anew;
        `capacity` pins the compact step's ladder rung (the escalation
        rerun re-enters here with the next rung up)."""
        if plan is None:
            plan = self._plan(lanes, device=device, capacity=capacity)
        if plan.used:
            raise ValueError(
                "a lane plan is single-use: its initial state buffer is "
                "donated at execution — warm_compile a fresh one")
        plan.used = True
        t0 = time.perf_counter()
        state = plan.compiled(*plan.args)
        plan.args = None      # the donated state buffer is gone anyway
        return _PendingLanes(self, state.stats, len(plan.lane_triples),
                             plan.lane_triples, plan.fault_sets,
                             plan.compile_s, plan.compile_count, t0,
                             plan.placement, plan.pad_fraction,
                             plan.grant_form, plan.capacity, plan.rows,
                             plan.superstep, plan.device)

    def run_lanes(self, lanes, device=None) -> LaneRun:
        """The fully general lane axis: one compiled batched scan over an
        arbitrary list of `(offered_per_chip, seed, faults)` lane triples,
        where `faults` is a `FaultSet`, a warm `FaultSchedule`, or None.

        Each lane's fault state COMPOSES on top of the sweep's base
        `faults` (`None` means "just the base faults").  When any lane
        carries a `FaultSchedule`, EVERY lane is promoted to a schedule
        (cold sets become single-epoch schedules) so the lane pytrees
        share one epoch-stacked structure — a mixed warm/cold
        (rates x seeds x schedules) grid still stacks into one dense
        batch.  When every composed lane ends up with the same fault state
        the shared-lane fast path is used (the fault pytree broadcasts
        instead of stacking), otherwise each distinct state builds its
        lane tables once and the step vmaps over the stacked lane axis —
        either way ONE dispatch, at most one jit compile.

        With multiple devices the lane axis is `shard_map`ped across
        them (ghost-padded to a device multiple); results stay lane-for-
        lane bit-identical to the single-device run.

        Returns a `LaneRun` (`results` one `SimResult` per lane in
        order, the compile/run wall split, and the composed per-lane
        fault states).
        """
        return self.run_lanes_async(lanes, device=device).finish()

    def run(self, rates, seeds=None) -> SweepResult:
        cfg = self.cfg
        rates = [float(r) for r in rates]
        seeds = [cfg.seed] if seeds is None else [int(s) for s in seeds]
        R, S = len(rates), len(seeds)
        if R * S == 0:
            raise ValueError(
                f"sweep needs >= 1 rate and >= 1 seed (got {R} rates, "
                f"{S} seeds)")
        run = self.run_lanes([(r, s, None) for r in rates for s in seeds])
        flat = run.results
        results = [[flat[i * S + j] for j in range(S)] for i in range(R)]
        return SweepResult(rates=rates, seeds=seeds, results=results,
                           compile_count=run.compile_count,
                           wall_s=run.wall_s, compile_s=run.compile_s,
                           placement=run.placement,
                           pad_fraction=run.pad_fraction,
                           grant_form=run.grant_form,
                           occupancy_peak=run.occupancy_peak,
                           compact_capacity=run.compact_capacity,
                           superstep=run.superstep,
                           escalations=run.escalations,
                           escalation_compiles=run.escalation_compiles)

    def run_faults(self, offered_per_chip: float, fault_grid,
                   seeds=None) -> SweepResult:
        """Degraded-throughput grid: one lane per (fault set, seed), all at
        the same offered load, in ONE compiled batched scan.

        `fault_grid` is a list of rows; row i is either one `FaultSet` /
        warm `FaultSchedule` (shared by every seed lane of that row) or a
        per-seed list `[FaultSet | FaultSchedule, ...]` (e.g.
        independently sampled failures per seed).  Rows map to
        `SweepResult.results` rows; `fault_fracs[i]` records row i's mean
        failed-link fraction (a schedule reports its final epoch).

        When the sweep itself was constructed with `faults`, every grid
        entry COMPOSES on top of that base set (an empty-FaultSet row
        means "just the base faults", not "pristine"); an invalid
        composition raises from `validate_faults`.
        """
        cfg = self.cfg
        seeds = [cfg.seed] if seeds is None else [int(s) for s in seeds]
        S = len(seeds)
        rows = [list(fs) if isinstance(fs, (list, tuple)) else [fs] * S
                for fs in fault_grid]
        if not rows or any(len(r) != S for r in rows):
            raise ValueError("fault_grid rows must match the seed count")
        F = len(rows)
        run = self.run_lanes(
            [(offered_per_chip, seeds[j], rows[i][j])
             for i in range(F) for j in range(S)])
        flat, fsets = run.results, run.fault_sets
        results = [[flat[i * S + j] for j in range(S)] for i in range(F)]
        fracs = [float(np.mean(
            [0.0 if f is None
             else final_faults(f).frac_links_failed(self.net)
             for f in fsets[i * S:(i + 1) * S]])) for i in range(F)]
        return SweepResult(rates=[offered_per_chip] * F, seeds=seeds,
                           results=results, compile_count=run.compile_count,
                           wall_s=run.wall_s, compile_s=run.compile_s,
                           fault_fracs=fracs, placement=run.placement,
                           pad_fraction=run.pad_fraction,
                           grant_form=run.grant_form,
                           occupancy_peak=run.occupancy_peak,
                           compact_capacity=run.compact_capacity,
                           superstep=run.superstep,
                           escalations=run.escalations,
                           escalation_compiles=run.escalation_compiles)
