"""Full reliability lifecycle: repair (shrinking) epochs, the
router-death reaper, and the wafer-fleet Monte Carlo spec.

Three pillars, matching the acceptance criteria:

  * repair epochs — LIFO-reverting `FaultSpec.repairs` sampling, engine
    runs across a shrink, per-epoch + transition deadlock proofs in all
    three vc_modes, and the degenerate static repair schedule
    bit-identical to its cold equivalent;
  * the router-death reaper — exact conservation (generated ==
    delivered + dropped + reaped + in-flight, via the shared
    `conservation_trace` helper) on jnp, fused, AND compact steps,
    trace-for-trace identical across the impls, with the stranded gauge
    draining to zero (non-increasing) once injection stops and the park
    age elapses;
  * the wafer fleet — `FleetSpec` validation/lowering/round-trip, the
    registered `smoke_fleet` scenario, the multi-tenant serve inbox,
    and a tiny end-to-end `run_fleet` with shared executables.
"""
import json

import numpy as np
import pytest

from conftest import conservation_trace
from repro.core import routing as R
from repro.core import topology as T
from repro.core import traffic as TR
from repro.core.engine import sweep as sweep_mod
from repro.core.engine.state import resolve_reap_age
from repro.core.simulator import SimConfig, Simulator

IMPLS = ("jnp", "fused", "compact")


@pytest.fixture(scope="module")
def small_net():
    return T.build_switchless(
        T.SwitchlessParams(a=1, b=2, m=2, n=4, noc=2, g=4), "rel-small")


@pytest.fixture(scope="module")
def multi_wg_net():
    return T.build_switchless(
        T.SwitchlessParams(a=2, b=2, m=2, n=4, noc=2, g=5), "rel-multiwg")


def _link_faults(net, frac, seed, vc_mode="updown", base=None):
    return T.sample_link_faults(net, frac, np.random.default_rng(seed),
                                types=(T.MESH, T.LOCAL, T.GLOBAL),
                                vc_mode=vc_mode, base=base)


def _router_faults(net, num, seed, vc_mode="updown", base=None):
    return T.sample_router_faults(net, num, np.random.default_rng(seed),
                                  vc_mode=vc_mode, base=base)


# --- repair (shrinking) epochs -----------------------------------------------

def test_schedule_allows_shrink_and_full_recovery(small_net):
    f = _link_faults(small_net, 0.08, 2)
    sch = T.FaultSchedule(((0, T.FaultSet()), (40, f), (120, T.FaultSet())))
    assert sch.num_epochs == 3 and not sch.is_static
    assert sch.final.is_empty          # fully recovered
    assert sch.epoch_at(119) == 1 and sch.epoch_at(120) == 2


def test_faultspec_repairs_revert_lifo(multi_wg_net):
    """`repairs` revert growth increments last-broken-first-fixed, so
    every repair epoch's fault set is an already-validated wear-out
    state; equal lengths mean the wafer fully recovers."""
    from repro.exp import FaultSpec
    spec = FaultSpec(kind="routers", num=2, seed=7,
                     onsets=(50, 100), repairs=(150, 200))
    sch = spec.sample(multi_wg_net, "updown", lane_seed=1)
    assert isinstance(sch, T.FaultSchedule) and sch.num_epochs == 5
    cycles = [c for c, _ in sch.epochs]
    assert cycles == [0, 50, 100, 150, 200]
    sets = [s for _, s in sch.epochs]
    assert sets[3] == sets[1]          # first repair reverts increment 2
    assert sets[4] == sets[0] == T.FaultSet()   # full recovery
    assert set(sets[2].dead_routers) > set(sets[1].dead_routers)


def test_repair_schedule_deadlock_free_all_vc_modes(multi_wg_net):
    """Acceptance: a shrinking schedule proves deadlock-free in all 3
    vc_modes — per-epoch CDG acyclicity AND the in-flight transition
    proof across the shrink (resumed down-phase walks on the recovered
    subgraph's recomputed rank order)."""
    net = multi_wg_net
    rng = np.random.default_rng(11)
    for mode in ("baseline", "updown", "updown_merged"):
        f1 = _link_faults(net, 0.05, 13, vc_mode=mode)
        f2 = _link_faults(net, 0.05, 17, vc_mode=mode, base=f1)
        sch = T.FaultSchedule(((0, T.FaultSet()), (60, f1), (120, f2),
                               (180, f1)))          # shrink back to f1
        sch.validate(net, mode)
        edges = R.assert_schedule_deadlock_free(net, mode, True, rng, sch,
                                                n_pairs=900)
        assert len(edges) == 4 and all(e > 0 for e in edges)


def test_static_repair_schedule_bit_identical_to_cold(small_net):
    """Acceptance: a repair-structured schedule whose fault set never
    changes reproduces the equivalent cold run bit-for-bit, in the same
    single-compile grid as genuinely shrinking lanes."""
    net = small_net
    f = _link_faults(net, 0.08, 19)
    cfg = SimConfig(warmup=80, measure=320, vc_mode="updown",
                    vcs_per_class=2)
    sim = Simulator(net, cfg, TR.uniform(net))
    # repair shape (grow @150, repair @300) with identical sets: the
    # engine must treat the two epoch swaps as no-ops
    static_repair = T.FaultSchedule(((0, f), (150, f), (300, f)))
    shrinking = T.FaultSchedule(((0, f), (150, _link_faults(
        net, 0.05, 23, base=f)), (300, f)))
    before = sweep_mod.compile_counter()
    grid = sim.sweep_faults(0.3, [f, static_repair, shrinking],
                            seeds=(0, 1))
    assert sweep_mod.compile_counter() - before == 1
    for j in range(2):
        cold, rep = grid.result(0, j), grid.result(1, j)
        assert rep.delivered_pkts == cold.delivered_pkts
        assert rep.generated_pkts == cold.generated_pkts
        assert rep.dropped_pkts == cold.dropped_pkts
        assert rep.avg_latency == cold.avg_latency
        assert rep.hops_by_type == cold.hops_by_type


def test_faultspec_level_static_repair_matches_pristine(small_net):
    """A sampled repair schedule that never grows (num=0) runs the
    repair machinery end to end and matches the pristine run exactly."""
    from repro.exp import FaultSpec
    net = small_net
    sch = FaultSpec(kind="routers", num=0, onsets=(60,), repairs=(140,),
                    per_seed=False).sample(net, "updown")
    assert sch.num_epochs == 3 and all(s.is_empty for _, s in sch.epochs)
    cfg = SimConfig(warmup=50, measure=250, vc_mode="updown",
                    vcs_per_class=2)
    sim = Simulator(net, cfg, TR.uniform(net))
    r_sch = sim.run(0.3, faults=sch)
    r_prist = sim.run(0.3)
    assert r_sch.delivered_pkts == r_prist.delivered_pkts
    assert r_sch.generated_pkts == r_prist.generated_pkts
    assert r_sch.avg_latency == r_prist.avg_latency


def test_repair_recovers_delivery(small_net):
    """Repairing a dead router mid-run recovers delivery: without the
    repair, every packet destined to its terminals strands forever;
    with it, the stranded population revives and delivers (the
    deterministic form of the recovery effect — link-fault repair gains
    drown in contention noise on a net this small)."""
    net = small_net
    rf = _router_faults(net, 2, 29)
    cfg = SimConfig(warmup=0, measure=800, vc_mode="updown",
                    vcs_per_class=2)
    sim = Simulator(net, cfg, TR.uniform(net))
    warm = T.FaultSchedule(((0, T.FaultSet()), (150, rf)))
    repaired = T.FaultSchedule(((0, T.FaultSet()), (150, rf),
                                (350, T.FaultSet())))
    r_warm = sim.run(0.25, faults=warm)
    r_rep = sim.run(0.25, faults=repaired)
    assert r_warm.stranded_pkts > 0
    assert r_rep.stranded_pkts == 0
    assert r_rep.delivered_pkts > r_warm.delivered_pkts
    assert r_warm.dropped_pkts == r_rep.dropped_pkts == 0


# --- conservation matrix: fault lifecycle x step impl ------------------------

@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("fkind", ["pristine", "cold", "warm", "repair"])
def test_conservation_matrix(small_net, fkind, impl):
    """The conservation invariant holds at every cycle for every fault
    lifecycle on every step impl, and the network drains completely once
    injection stops (link faults keep destinations alive: nothing
    strands, nothing is reaped)."""
    net = small_net
    f = _link_faults(net, 0.10, 31)
    faults = dict(
        pristine=None,
        cold=f,
        warm=T.FaultSchedule(((0, T.FaultSet()), (40, f))),
        repair=T.FaultSchedule(((0, T.FaultSet()), (30, f),
                                (90, T.FaultSet()))))[fkind]
    cfg = SimConfig(warmup=0, measure=1, vc_mode="updown",
                    vcs_per_class=2, step_impl=impl)
    trace = conservation_trace(net, cfg, faults=faults, cycles=560,
                               rate=0.06, stop_inject_at=100)
    last = trace[-1]
    assert last["generated"] > 100
    assert last["inflight"] == 0, "network must drain once injection stops"
    assert last["reaped"] == 0 and last["stranded"] == 0


def test_conservation_across_repair_boundary_strands_then_revives(small_net):
    """Router death strands parked packets on the gauge; the repair
    epoch revives them (reaper off: nothing is ever dropped or reaped,
    the stranded population returns to flight and delivers)."""
    net = small_net
    rf = _router_faults(net, 2, 37)
    sch = T.FaultSchedule(((0, T.FaultSet()), (40, rf),
                           (160, T.FaultSet())))
    cfg = SimConfig(warmup=0, measure=1, vc_mode="updown", vcs_per_class=2,
                    step_impl="jnp")
    trace = conservation_trace(net, cfg, faults=sch, cycles=700,
                               rate=0.05, stop_inject_at=90)
    assert max(r["stranded"] for r in trace) > 0, "router death must strand"
    last = trace[-1]
    assert last["inflight"] == 0 and last["stranded"] == 0
    assert last["reaped"] == 0 and last["dropped"] == 0
    assert last["generated"] == last["delivered"]


# --- router-death reaper -----------------------------------------------------

def test_resolve_reap_age_precedence(monkeypatch):
    monkeypatch.delenv("REPRO_REAP_AGE", raising=False)
    assert resolve_reap_age(SimConfig()) == 0
    assert resolve_reap_age(SimConfig(reap_age=25)) == 25
    monkeypatch.setenv("REPRO_REAP_AGE", "40")
    assert resolve_reap_age(SimConfig()) == 40
    assert resolve_reap_age(SimConfig(reap_age=25)) == 25
    with pytest.raises(ValueError):
        SimConfig(reap_age=-1)


def test_reaper_spec_threads_to_simconfig():
    from repro.exp import ReaperSpec, RoutingSpec, SweepAxes
    axes = SweepAxes(rates=(0.3,), warmup=10, measure=20)
    rs = RoutingSpec(reaper={"park_age": 30})
    assert rs.reaper == ReaperSpec(park_age=30)
    assert rs.to_simconfig(axes).reap_age == 30
    assert RoutingSpec.from_dict(rs.to_dict()) == rs
    assert RoutingSpec().to_simconfig(axes).reap_age == 0
    with pytest.raises(ValueError):
        ReaperSpec(park_age=-1)


def test_reaper_drains_stranded_population(small_net):
    """Acceptance (small-scale form): with the reaper on, a router-death
    run's stranded gauge is non-increasing once the park age elapses
    after the last injection, drains to zero, and the books balance
    exactly — identically on jnp, fused, and compact."""
    net = small_net
    rf = _router_faults(net, 2, 41)
    sch = T.FaultSchedule(((0, T.FaultSet()), (50, rf)))
    reap_age, stop = 60, 120
    traces = {}
    for impl in IMPLS:
        cfg = SimConfig(warmup=0, measure=1, vc_mode="updown",
                        vcs_per_class=2, step_impl=impl,
                        reap_age=reap_age)
        traces[impl] = conservation_trace(net, cfg, faults=sch,
                                          cycles=640, rate=0.05,
                                          stop_inject_at=stop)
    for impl, trace in traces.items():
        last = trace[-1]
        assert last["reaped"] > 0, f"{impl}: router death must reap"
        assert max(r["stranded"] for r in trace) > 0
        # every injected packet has itime < stop, so by stop + reap_age
        # every parked packet has been reaped: the gauge hits zero and
        # stays there (non-increasing => bounded steady state)
        settled = [r["stranded"] for r in trace if r["t"] >= stop + reap_age]
        assert settled and all(s == 0 for s in settled), impl
        assert all(a >= b for a, b in zip(settled, settled[1:])), impl
        assert last["inflight"] == 0 and last["stranded"] == 0
        assert last["generated"] == (last["delivered"] + last["dropped"]
                                     + last["reaped"])
    # the reaper is bit-identical across the three step impls
    assert traces["fused"] == traces["jnp"]
    assert traces["compact"] == traces["jnp"]


@pytest.mark.slow
def test_reaper_drains_at_radix32(small_net):
    """Acceptance (paper-scale form): the same drain property on the
    radix-32-class network of the yield benchmark."""
    from repro.exp import TopologySpec
    net = TopologySpec.preset("radix32_switchless", g=2,
                              label="rel-radix32").build()
    rf = _router_faults(net, 4, 43)
    sch = T.FaultSchedule(((0, T.FaultSet()), (60, rf)))
    reap_age, stop = 80, 160
    cfg = SimConfig(warmup=0, measure=1, vc_mode="updown",
                    vcs_per_class=2, step_impl="fused",
                    reap_age=reap_age)
    trace = conservation_trace(net, cfg, faults=sch, cycles=1350,
                               rate=0.06, stop_inject_at=stop)
    last = trace[-1]
    assert last["reaped"] > 0 and max(r["stranded"] for r in trace) > 0
    settled = [r["stranded"] for r in trace if r["t"] >= stop + reap_age]
    assert settled and all(s == 0 for s in settled)
    assert last["inflight"] == 0
    assert last["generated"] == (last["delivered"] + last["dropped"]
                                 + last["reaped"])


def test_reaper_respects_park_age(small_net):
    """No packet is reaped before its generation age reaches the park
    age: a pristine run (nothing ever parks) reaps nothing even with an
    aggressive reaper, and a longer park age reaps no more packets than
    a shorter one on the same fault run."""
    net = small_net
    cfg = SimConfig(warmup=0, measure=1, vc_mode="updown",
                    vcs_per_class=2, reap_age=5)
    trace = conservation_trace(net, cfg, cycles=200, rate=0.08,
                               stop_inject_at=150)
    assert trace[-1]["reaped"] == 0
    rf = _router_faults(net, 2, 41)
    sch = T.FaultSchedule(((0, T.FaultSet()), (50, rf)))
    reaped = {}
    for age in (40, 120):
        cfg = SimConfig(warmup=0, measure=1, vc_mode="updown",
                        vcs_per_class=2, reap_age=age)
        reaped[age] = conservation_trace(
            net, cfg, faults=sch, cycles=400, rate=0.08,
            stop_inject_at=150)[-1]["reaped"]
    assert reaped[40] >= reaped[120] > 0


# --- wafer-fleet Monte Carlo -------------------------------------------------

def test_fleet_spec_validates():
    from repro.exp import FaultSpec, FleetSpec, RoutingSpec, TopologySpec
    topo = TopologySpec.switchless(a=1, b=2, m=2, n=4, noc=2, g=4,
                                   label="fleet-t")
    routing = RoutingSpec(vc_mode="updown", vcs_per_class=2)
    ok = FleetSpec(name="f", topology=topo, routing=routing,
                   levels=(FaultSpec(),
                           FaultSpec(kind="routers", num=1, seed=1)),
                   samples=4)
    assert ok.samples == 4
    with pytest.raises(ValueError, match="per_seed"):
        FleetSpec(name="f", topology=topo, routing=routing,
                  levels=(FaultSpec(kind="routers", num=1,
                                    per_seed=False),))
    with pytest.raises(ValueError):
        FleetSpec(name="f", topology=topo, routing=routing,
                  levels=(FaultSpec(),), samples=0)
    with pytest.raises(ValueError):
        FleetSpec(name="f", topology=topo, routing=routing,
                  levels=(FaultSpec(),), yield_threshold=1.5)
    assert FleetSpec.from_dict(ok.to_dict()) == ok


def test_fleet_lowers_to_seed_lanes_and_is_registered():
    from repro.exp import get_scenario, list_scenarios
    from repro.exp.fleet import smoke_fleet
    fleet = smoke_fleet()
    exp = fleet.to_experiment()
    assert exp.axes.seeds == tuple(range(fleet.samples))
    assert exp.axes.rates == (fleet.offered,)
    assert len(exp.axes.faults) == len(fleet.levels)
    # registered under the fleet's name -> covered by `check --spec`
    assert "smoke_fleet" in list_scenarios()
    assert get_scenario("smoke_fleet").axes == exp.axes


def test_fleet_inbox_is_multi_tenant(tmp_path):
    from repro.exp import ExperimentSpec, FaultSpec, FleetSpec, \
        RoutingSpec, TopologySpec, fleet_inbox
    fleet = FleetSpec(
        name="inboxed",
        topology=TopologySpec.switchless(a=1, b=2, m=2, n=4, noc=2, g=4,
                                         label="fleet-t"),
        routing=RoutingSpec(vc_mode="updown", vcs_per_class=2),
        levels=(FaultSpec(), FaultSpec(kind="routers", num=1, seed=1)),
        samples=3)
    paths = fleet_inbox(fleet, str(tmp_path))
    assert len(paths) == 3
    tenants = set()
    for i, p in enumerate(sorted(paths)):
        sub = json.loads(open(p).read())
        tenants.add(sub["tenant"])
        spec = ExperimentSpec.from_dict(sub["spec"])
        assert spec.axes.seeds == (i,)      # one wafer per submission
        assert spec.axes.faults == fleet.to_experiment().axes.faults
    assert tenants == {"wafer0", "wafer1", "wafer2"}


def test_run_fleet_end_to_end_shares_executables():
    from repro.exp import FaultSpec, FleetSpec, RoutingSpec, TopologySpec
    from repro.exp.fleet import run_fleet
    fleet = FleetSpec(
        name="tiny_fleet",
        topology=TopologySpec.switchless(a=1, b=2, m=2, n=4, noc=2, g=4,
                                         label="fleet-t"),
        routing=RoutingSpec(vc_mode="updown", vcs_per_class=2,
                            reaper={"park_age": 50}),
        levels=(FaultSpec(), FaultSpec(kind="routers", num=1, seed=1)),
        samples=4, offered=0.3, warmup=30, measure=150)
    res = run_fleet(fleet)
    assert len(res.records) == 2
    for rec in res.records:
        assert rec["samples"] == 4
        assert set(rec["throughput"]) == {"p10", "p50", "p90"}
        assert rec["compile_count"] <= 1
        assert rec["yield_frac"] <= 1.0
    prist, faulty = res.records
    assert prist["reaped_total"] == 0
    assert prist["throughput"]["p50"] >= faulty["throughput"]["p50"]
