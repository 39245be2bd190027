"""Flit-level network simulator in JAX (paper Sec. V) — facade.

Dataflow re-architecture of a cycle-accurate NoC simulator for XLA: all
router state lives in fixed-shape arrays over (channel, VC, slot) and one
`lax.scan` advances the whole network a cycle at a time.

The cycle itself is implemented by the modular engine under
``repro.core.engine`` (inject -> arbitrate -> apply -> stats over a pytree
``SimState``); this module keeps the stable public API: ``SimConfig``,
``SimResult``, ``Simulator`` (compile-once, per-rate ``run``), and a
``sweep`` that now executes the whole load-latency curve as ONE batched
`lax.scan` via ``engine.sweep.BatchedSweep``.

``Simulator`` is the imperative compatibility facade.  New scenario code
should describe runs declaratively with ``repro.exp`` (``ExperimentSpec``
-> ``run_experiment``), which lowers topology x traffic x routing x fault
grids onto the same engine with one compile per grid; benchmarks and
examples in this repo construct their runs that way.

Microarchitecture model
  * input-queued routers, virtual cut-through at packet granularity
    (PKT flits move together; a packet is visible downstream after the
    channel pipeline latency; the channel stays busy PKT/bw cycles, which
    models serialization and heterogeneous link bandwidth);
  * per-(channel, VC) input buffers of `buf_pkts` packets (32-flit buffers
    with 4-flit packets by default, as Table IV);
  * age-based (oldest-first) output arbitration, one packet per output
    channel per cycle;
  * credit-based flow control (a slot is reserved at send time);
  * terminals inject through an explicit 1 flit/cycle injection channel and
    eject through a 1 flit/cycle ejection channel — in the switch-based
    baseline this is the single terminal-to-switch link that caps injection
    at 1 flit/cycle/chip, in the switch-less network every on-wafer router
    owns one, giving the paper's 4 injection ports per chip.

Routing modes: "min", "val" (Valiant non-minimal), "val_restricted"
(misroute only to lower W-groups; pairs with the 3-VC reduced scheme), and
"ugal" (UGAL-G adaptive; beyond-paper extension).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax

from .topology import FaultSchedule, FaultSet, Network, compose_faults
from .engine.arbitrate import GRANT_IMPLS
from .engine.state import build_lane, make_state as _engine_make_state
from .engine.step import STEP_IMPLS, make_step, run_scan
from .engine.stats import finalize
from .engine.sweep import (BatchedSweep, SweepResult, offered_to_rate_pkt)


@dataclass(frozen=True)
class SimConfig:
    pkt_len: int = 4          # flits per packet (Table IV)
    buf_pkts: int = 8         # input buffer: 32 flits / 4 = 8 packets
    srcq_pkts: int = 64       # source queue depth (packets)
    vcs_per_class: int = 2    # physical VCs per deadlock class (HOL relief)
    warmup: int = 2000
    measure: int = 8000
    vc_mode: str = "baseline"          # "baseline" | "updown" | "updown_merged"
    route_mode: str = "min"            # "min" | "val" | "val_restricted" | "ugal"
    ugal_threshold: int = 3
    seed: int = 0
    # arbitration grant implementation: "jnp" (the jax.ops.segment_min
    # path, default and oracle) or "pallas" (the fused netsim kernel,
    # `repro.kernels.netsim` — bit-identical, TPU-ready fast path)
    grant_impl: str = "jnp"
    # cycle-step implementation: "fused" (the default: the
    # per-channel-winner step with the route-once-per-hop cache,
    # `engine.fused` — bit-identical to the oracle, and the only step the
    # 2-D (lanes x shards) channel-sharded mesh can run), "jnp" (the
    # modular phase pipeline, the oracle, by name), or "compact" (the
    # fused step with live rows compacted into a capacity-C active set
    # before arbitration — bit-identical, occupancy-proportional; see
    # `engine.fused.make_compact_step` and REPRO_COMPACT_CAP)
    step_impl: str = "fused"
    # router-death reaper park age (cycles): packets parked on the -1
    # non-channel (destination dead / unroutable) are dropped once their
    # generation age reaches this, tallied in `SimStats.reaped` /
    # `SimResult.reaped_pkts` — disjoint from `dropped`, so
    # ``generated == delivered + dropped + reaped + in-flight`` stays
    # exact.  0 disables the reaper (the step compiles no reap logic);
    # the REPRO_REAP_AGE env knob supplies a process-wide default when
    # the config leaves it off.  See `engine.state.resolve_reap_age`.
    reap_age: int = 0

    def __post_init__(self):
        if self.grant_impl not in GRANT_IMPLS:
            raise ValueError(
                f"unknown grant_impl {self.grant_impl!r}; "
                f"valid: {GRANT_IMPLS}")
        if self.step_impl not in STEP_IMPLS:
            raise ValueError(
                f"unknown step_impl {self.step_impl!r}; "
                f"valid: {STEP_IMPLS}")
        if self.reap_age < 0:
            raise ValueError(f"reap_age must be >= 0, got {self.reap_age}")

    @property
    def nonminimal(self) -> bool:
        return self.route_mode != "min"


@dataclass
class SimResult:
    offered_per_chip: float
    throughput_per_chip: float     # accepted/delivered flits per cycle per chip
    avg_latency: float             # cycles, generation -> ejection
    delivered_pkts: int
    generated_pkts: int
    dropped_pkts: int              # source-queue overflow (backlog)
    hops_by_type: dict
    avg_hops_by_type: dict = field(default_factory=dict)
    stranded_pkts: int = 0         # parked on the -1 non-channel at exit
                                   # (warm faults left them unroutable);
                                   # seed-averaged rows report the exact
                                   # per-lane MAX (see mean_over_seeds)
    stranded_mean: float = 0.0     # exact mean of stranded_pkts over the
                                   # seed lanes (== stranded_pkts for a
                                   # single lane)
    reaped_pkts: int = 0           # dropped by the router-death reaper
                                   # (age-based; disjoint from dropped)
    occupancy_peak: int = 0        # high-water mark of live request rows
                                   # (whole run incl. warmup; the compact
                                   # step's capacity certificate)

    def row(self) -> str:
        return (f"{self.offered_per_chip:.3f},{self.throughput_per_chip:.3f},"
                f"{self.avg_latency:.1f}")


def make_state(net: Network, cfg: SimConfig, NV: int):
    """Compat shim: fresh engine `SimState` (was an ad-hoc dict)."""
    return _engine_make_state(net, cfg, NV)


class Simulator:
    """Compile-once-per-(net, cfg, pattern) simulator; sweep rates cheaply.

    ``run`` executes one offered rate sequentially (one `lax.scan`);
    ``sweep`` batches every (rate, seed) lane into a single scan.
    """

    def __init__(self, net: Network, cfg: SimConfig, pattern,
                 inject_mask=None,
                 faults: FaultSet | FaultSchedule | None = None):
        from .traffic import as_pattern
        self.net, self.cfg = net, cfg
        self.terms_per_chip = net.num_terminals / net.num_chips
        pattern = as_pattern(pattern, inject_mask)  # mask rides the pattern
        self.step, self.consts = make_step(net, cfg, pattern)
        self.NV = self.consts["NV"]
        self.faults = faults
        self.lane = build_lane(net, cfg, faults)
        self._batched = BatchedSweep(net, cfg, pattern,
                                     step=self.step, consts=self.consts,
                                     faults=faults, lane=self.lane)

    def run(self, offered_per_chip: float, seed: int | None = None,
            faults: FaultSet | FaultSchedule | None = None) -> SimResult:
        """One offered rate, sequentially.  `faults` (a cold set or a warm
        schedule) composes on top of the instance fault state for this run
        only (same semantics as `sweep_faults` grid entries) — fault data
        is a traced step argument, so switching fault sets reuses the
        compiled scan (a schedule's epoch-stacked lane compiles once per
        epoch-count shape)."""
        cfg = self.cfg
        rate_pkt = offered_to_rate_pkt(offered_per_chip, cfg,
                                       self.terms_per_chip)
        state0 = _engine_make_state(self.net, cfg, self.NV)
        cycles = cfg.warmup + cfg.measure
        key = jax.random.PRNGKey(cfg.seed if seed is None else seed)
        if faults is None:
            lane, chips = self.lane, self._batched._chips(self.faults)
        else:
            faults = compose_faults(self.faults, faults)
            lane = build_lane(self.net, cfg, faults)
            chips = self._batched._chips(faults)
        state = run_scan(self.step, cycles, cfg.warmup,
                         state0, jax.numpy.float32(rate_pkt), key, lane)
        return finalize(state.stats, cfg, offered_per_chip, chips)

    def sweep(self, rates, seeds=None) -> list[SimResult]:
        """Batched load-latency curve; one jit compile + one device dispatch.

        Returns one (seed-averaged) `SimResult` per rate, in order, matching
        the historical ``[self.run(r) for r in rates]`` contract.
        """
        return self.sweep_grid(rates, seeds).mean_over_seeds()

    def sweep_grid(self, rates, seeds=None) -> SweepResult:
        """Full (rate x seed) grid of `SimResult`s plus sweep metadata."""
        return self._batched.run(rates, seeds)

    def sweep_faults(self, offered_per_chip: float, fault_grid,
                     seeds=None) -> SweepResult:
        """Degraded-throughput grid: one lane per (fault set, seed) at a
        fixed offered load, all in one compiled batched scan (see
        `BatchedSweep.run_faults`)."""
        return self._batched.run_faults(offered_per_chip, fault_grid, seeds)


def saturation_throughput(results: list[SimResult]) -> float:
    """Max accepted throughput over a sweep (flits/cycle/chip)."""
    return max(r.throughput_per_chip for r in results)
