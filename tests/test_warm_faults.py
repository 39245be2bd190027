"""Warm (time-varying) faults: FaultSchedule semantics, per-epoch
deadlock freedom, the routing-package public API, cold/warm engine parity,
packet conservation across an epoch boundary, and the fault-aware
adaptive misroute stage."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import engine
from repro.core import routing as R
from repro.core import topology as T
from repro.core import traffic as TR
from repro.core.engine import build_lane, make_state
from repro.core.engine import sweep as sweep_mod
from repro.core.simulator import SimConfig, Simulator


@pytest.fixture(scope="module")
def small_net():
    return T.build_switchless(
        T.SwitchlessParams(a=1, b=2, m=2, n=4, noc=2, g=4), "warm-small")


@pytest.fixture(scope="module")
def multi_wg_net():
    return T.build_switchless(
        T.SwitchlessParams(a=2, b=2, m=2, n=4, noc=2, g=5), "warm-multiwg")


def _link_faults(net, frac, seed, types=(T.MESH, T.LOCAL, T.GLOBAL),
                 vc_mode="updown", base=None):
    return T.sample_link_faults(net, frac, np.random.default_rng(seed),
                                types=types, vc_mode=vc_mode, base=base)


# --- FaultSchedule semantics -------------------------------------------------

def test_schedule_construction_validates(small_net):
    f = _link_faults(small_net, 0.05, 0)
    with pytest.raises(ValueError):
        T.FaultSchedule(())                       # no epochs
    with pytest.raises(ValueError):
        T.FaultSchedule(((5, f),))                # first epoch not at 0
    with pytest.raises(ValueError):
        T.FaultSchedule(((0, f), (10, f), (10, f)))  # not increasing
    with pytest.raises(ValueError):
        T.FaultSchedule(((0, "nope"),))           # not a FaultSet
    sch = T.FaultSchedule(((0, T.FaultSet()), (100, f)))
    assert sch.num_epochs == 2 and not sch.is_static and not sch.is_empty
    assert sch.final == f
    assert sch.epoch_at(0) == 0 and sch.epoch_at(99) == 0
    assert sch.epoch_at(100) == 1 and sch.epoch_at(10**6) == 1
    assert T.FaultSchedule.cold(f).is_static
    assert T.as_fault_schedule(None).is_empty
    assert T.as_fault_schedule(f).final == f
    assert T.final_faults(sch) == f and T.final_faults(f) == f
    # schedules are hashable (lane memoization keys)
    assert len({sch, sch, T.FaultSchedule.cold(f)}) == 2


def test_schedule_compose_and_union_base(small_net):
    f1 = _link_faults(small_net, 0.04, 1)
    f2 = _link_faults(small_net, 0.04, 2)
    sch = T.FaultSchedule(((0, T.FaultSet()), (50, f1)))
    u = sch.union_base(f2)
    assert u.epochs[0] == (0, f2)
    assert u.epochs[1] == (50, f1.union(f2))
    # compose_faults: set x set, schedule x set, schedule x schedule
    assert T.compose_faults(f1, None) == f1
    assert T.compose_faults(None, sch) == sch
    assert T.compose_faults(f2, sch) == u
    sch2 = T.FaultSchedule(((0, T.FaultSet()), (80, f2)))
    m = T.compose_faults(sch, sch2)
    assert [c for c, _ in m.epochs] == [0, 50, 80]
    assert m.final == f1.union(f2)


def test_schedule_validate_rejects_unroutable_epoch(multi_wg_net):
    net = multi_wg_net
    # kill every global link of one W-group pair in the second epoch
    t = net.tables
    chs = []
    for r in range(t["glob_route_cg"].shape[-1]):
        cg = t["glob_route_cg"][0, 1, r]
        if cg >= 0:
            ch = t["ext_out"][cg, t["glob_route_port"][0, 1, r]]
            if ch >= 0:
                chs.append(int(ch))
    bad = T.FaultSchedule(((0, T.FaultSet()),
                           (40, T.FaultSet(dead_ch=tuple(chs)))))
    with pytest.raises(ValueError, match="cycle 40"):
        bad.validate(net, "updown")
    # building an engine lane validates every epoch too
    cfg = SimConfig(vc_mode="updown")
    with pytest.raises(ValueError):
        build_lane(net, cfg, bad)


# --- routing package ---------------------------------------------------------

def test_routing_package_public_api(multi_wg_net):
    """The routing/ package keeps the monolithic module's public API and
    adds the RoutePipeline protocol."""
    # historical imports (seed_reference and the engine rely on these)
    from repro.core.routing import (assert_deadlock_free, build_updown_tables,
                                    make_route_fn, make_route_kernel,
                                    meta_cg_count, meta_update, num_vcs,
                                    route_tables, trace_paths)
    net = multi_wg_net
    pipe = R.make_pipeline(net, "updown")
    assert isinstance(pipe, R.RoutePipeline)
    assert pipe.num_vcs(nonminimal=True) == num_vcs("switchless", "updown",
                                                    True)
    # bind() == make_route_fn: same outputs on the same inputs
    rf_a = pipe.bind()
    rf_b = make_route_fn(net, "updown")
    rng = np.random.default_rng(0)
    cur = jnp.asarray(rng.integers(0, net.num_nodes, 64))
    dest = jnp.asarray(rng.integers(0, net.num_terminals, 64))
    mis = jnp.full((64,), -1, jnp.int32)
    meta = jnp.zeros(64, jnp.int32)
    for a, b in zip(rf_a(cur, dest, mis, meta), rf_b(cur, dest, mis, meta)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # epoch_tables stacks one table set per epoch
    f = _link_faults(net, 0.06, 3)
    sch = T.FaultSchedule(((0, T.FaultSet()), (70, f)))
    starts, tabs = pipe.epoch_tables(sch)
    assert list(np.asarray(starts)) == [0, 70]
    for k, v in tabs.items():
        assert v.shape[0] == 2, k
    # epoch 0 tables == pristine tables bit-for-bit
    prist = route_tables(net, "updown")
    for k in prist:
        np.testing.assert_array_equal(np.asarray(tabs[k][0]),
                                      np.asarray(prist[k]))


def test_schedule_deadlock_free_every_epoch(multi_wg_net):
    net = multi_wg_net
    f1 = _link_faults(net, 0.05, 7)
    f2 = _link_faults(net, 0.05, 8, base=f1)
    sch = T.FaultSchedule(((0, T.FaultSet()), (60, f1), (120, f2)))
    rng = np.random.default_rng(1)
    edges = R.assert_schedule_deadlock_free(net, "updown", True, rng, sch,
                                            n_pairs=1500)
    assert len(edges) == 3 and all(e > 0 for e in edges)


def test_registered_warm_scenarios_deadlock_free_all_modes():
    """Acceptance: every epoch of every registered warm-fault scenario's
    sampled schedules is deadlock-free under all three vc_modes."""
    from repro.exp import registry
    rng = np.random.default_rng(5)
    checked = 0
    for name in registry.list_scenarios():
        spec = registry.get_scenario(name)
        warm = [f for f in spec.axes.faults if f.is_warm]
        if not warm:
            continue
        net = spec.topologies[0].build()
        for f in warm:
            sch = f.sample(net, spec.routings[0].vc_mode,
                           spec.axes.seeds[0])
            assert isinstance(sch, T.FaultSchedule)
            for mode in ("baseline", "updown", "updown_merged"):
                try:
                    sch.validate(net, mode)
                except ValueError:
                    # baseline routes deterministically and only
                    # tolerates GLOBAL-link faults; registered router /
                    # mesh fault populations (e.g. the fleet levels)
                    # are legitimately rejected there — the up*/down*
                    # modes must still prove out
                    assert mode == "baseline"
                    continue
                R.assert_schedule_deadlock_free(net, mode, True, rng, sch,
                                                n_pairs=600)
            checked += 1
    assert checked >= 2  # smoke_warm_faults + yield_curve populations


# --- engine parity and conservation ------------------------------------------

def test_static_schedule_matches_cold_run_faults_lane_for_lane(small_net):
    """Acceptance: the all-epochs-identical schedule reproduces the cold
    `run_faults` grid bit-for-bit, and a mixed (rates x seeds x schedules)
    grid — including different epoch counts — runs in ONE compile."""
    net = small_net
    f = _link_faults(net, 0.08, 11)
    cfg = SimConfig(warmup=107, measure=389, vc_mode="updown",
                    vcs_per_class=2)
    sim = Simulator(net, cfg, TR.uniform(net))
    static2 = T.FaultSchedule(((0, f), (251, f)))
    static3 = T.FaultSchedule(((0, f), (151, f), (301, f)))
    seeds = (0, 1)
    before = sweep_mod.compile_counter()
    grid = sim.sweep_faults(0.3, [f, static2, static3], seeds=seeds)
    assert sweep_mod.compile_counter() - before == 1
    assert grid.compile_count == 1
    for j in range(len(seeds)):
        cold = grid.result(0, j)
        for i in (1, 2):
            warm = grid.result(i, j)
            assert warm.delivered_pkts == cold.delivered_pkts
            assert warm.generated_pkts == cold.generated_pkts
            assert warm.dropped_pkts == cold.dropped_pkts
            assert warm.avg_latency == cold.avg_latency
            assert warm.hops_by_type == cold.hops_by_type


def test_warm_schedule_degrades_but_beats_cold(small_net):
    """A mid-run die-off sits between pristine and cold-from-0 delivery:
    the pre-onset cycles run at full capacity."""
    net = small_net
    f = _link_faults(net, 0.10, 23)
    cfg = SimConfig(warmup=0, measure=600, vc_mode="updown",
                    vcs_per_class=2)
    sim = Simulator(net, cfg, TR.uniform(net))
    warm = T.FaultSchedule(((0, T.FaultSet()), (300, f)))
    r_prist = sim.run(0.45)
    r_warm = sim.run(0.45, faults=warm)
    r_cold = sim.run(0.45, faults=f)
    assert r_cold.delivered_pkts <= r_warm.delivered_pkts \
        <= r_prist.delivered_pkts
    assert r_cold.delivered_pkts < r_prist.delivered_pkts


def test_conservation_across_epoch_boundary(small_net):
    """Acceptance (drain semantics): generated == delivered + in-flight +
    dropped at every cycle, across the epoch boundary, and the network
    drains completely once injection stops (no buffered packet is ever
    silently dropped when links die mid-run).  The per-cycle arithmetic
    lives in the shared `conservation_trace` helper (conftest.py), which
    test_reliability.py applies across the whole {pristine, cold, warm,
    repair} x {jnp, fused, compact} matrix."""
    from conftest import conservation_trace
    net = small_net
    f = _link_faults(net, 0.12, 31)
    sch = T.FaultSchedule(((0, T.FaultSet()), (40, f)))
    cfg = SimConfig(warmup=0, measure=1, vc_mode="updown", vcs_per_class=2)
    trace = conservation_trace(net, cfg, faults=sch, cycles=500,
                               rate=0.08, stop_inject_at=80)
    assert trace[40]["inflight"] > 0, "no traffic in flight at the boundary"
    last = trace[-1]
    assert last["generated"] > 100
    assert last["inflight"] == 0, "network must drain once injection stops"
    assert last["generated"] == last["delivered"] + last["dropped"]


def test_stranded_packet_request_never_granted(small_net):
    """A request for the -1 non-channel (warm-stranded packet) must never
    win arbitration or corrupt the trailing eject channel's accounting."""
    net = small_net
    cfg = SimConfig(vc_mode="updown", vcs_per_class=1, step_impl="jnp")
    consts, route_kernel = engine.build_consts(net, cfg)
    fl = build_lane(net, cfg)
    state = make_state(net, cfg, consts["NV"])
    # hand-build: one packet at the head of (channel 0, vc 0) whose route
    # is forced to -1 by a crafted all-dead next-hop table
    state = state.replace(
        b_count=state.b_count.at[0, 0].set(1),
        b_pkt=state.b_pkt.at[0, 0, 0].set(
            jnp.asarray([5, 0, -1, 0, 0], jnp.int32)))
    crafted = dict(fl, ud_nh=jnp.full_like(fl["ud_nh"], -1))
    arbitrate = engine.make_arbitrate_fn(net, cfg, consts, route_kernel)
    req, win, won_ch = arbitrate(state, 0, crafted)
    out0 = int(np.asarray(req.out)[0])
    assert out0 == -1
    assert not bool(np.asarray(win)[0])
    assert not np.asarray(won_ch)[-1], "phantom grant on trailing eject"


# --- fault-aware adaptive misrouting -----------------------------------------

def test_adaptive_lane_tables(multi_wg_net):
    """Pristine lanes carry identity adaptive tables; faulted lanes mask
    dead pairs and penalize degraded W-groups."""
    net = multi_wg_net
    cfg = SimConfig(route_mode="ugal", vc_mode="updown")
    fl0 = build_lane(net, cfg)
    assert bool(np.asarray(fl0["glob_ok"]).all())
    assert (np.asarray(fl0["wg_penalty"]) == 0).all()
    f = _link_faults(net, 0.15, 41, types=(T.MESH, T.LOCAL))
    fl = build_lane(net, cfg, f)
    pen = np.asarray(fl["wg_penalty"])
    assert pen.max() > 0
    frac = T.wg_channel_alive_frac(net, f)
    np.testing.assert_array_equal(
        pen, np.round(engine.state.UGAL_WG_PENALTY_SCALE * (1 - frac)))


def test_misroute_masked_by_global_liveness(multi_wg_net):
    """VAL candidates whose misroute path lost all global links fall back
    to minimal."""
    net = multi_wg_net
    cfg = SimConfig(route_mode="val", vcs_per_class=1)
    consts, _ = engine.build_consts(net, cfg)
    gen_mis = engine.make_misroute_fn(net, cfg, consts)
    fl = build_lane(net, cfg)
    T_ = net.num_terminals
    tpw = net.meta["terms_per_wg"]
    dest = jnp.full((T_,), (net.meta["g"] - 1) * tpw, dtype=jnp.int32)
    key = jax.random.PRNGKey(7)
    mis_ok = np.asarray(gen_mis(key, dest, jnp.zeros(
        (net.num_channels, consts["NV"]), jnp.int32), fl))
    assert (mis_ok >= 0).any()
    # kill the candidate set: no W-group pair keeps an alive global link
    dead = dict(fl, glob_ok=jnp.zeros_like(fl["glob_ok"]))
    mis_dead = np.asarray(gen_mis(key, dest, jnp.zeros(
        (net.num_channels, consts["NV"]), jnp.int32), dead))
    assert (mis_dead == -1).all()


def test_ugal_biased_away_from_degraded_wgroup(multi_wg_net):
    """The degradation penalty flips a borderline UGAL decision back to
    minimal for candidates in a degraded W-group."""
    net = multi_wg_net
    cfg = SimConfig(route_mode="ugal", vcs_per_class=1, ugal_threshold=3)
    consts, _ = engine.build_consts(net, cfg)
    gen_mis = engine.make_misroute_fn(net, cfg, consts)
    fl = build_lane(net, cfg)
    g = net.meta["g"]
    tpw = net.meta["terms_per_wg"]
    T_ = net.num_terminals
    wd = g - 1
    dest = jnp.full((T_,), wd * tpw, dtype=jnp.int32)
    # congest the minimal-path sensor so UGAL wants to misroute
    watch = np.asarray(fl["ugal_watch"])
    occ = np.zeros((net.num_channels, consts["NV"]), dtype=np.int32)
    occ[watch[:, wd, 0][watch[:, wd, 0] >= 0]] = cfg.buf_pkts
    key = jax.random.PRNGKey(9)
    mis_nopen = np.asarray(gen_mis(key, dest, jnp.asarray(occ), fl))
    took = mis_nopen >= 0
    assert took.any()
    # penalize EVERY candidate W-group heavily -> all decisions minimal
    pen = dict(fl, wg_penalty=jnp.full((g,), 64, jnp.int32))
    mis_pen = np.asarray(gen_mis(key, dest, jnp.asarray(occ), pen))
    assert (mis_pen == -1).all()


def test_warm_ugal_end_to_end(multi_wg_net):
    """A warm global die-off under adaptive routing still delivers (the
    smoke_warm_faults scenario shape, one compile)."""
    net = multi_wg_net
    sch = T.FaultSchedule(((0, T.FaultSet()),
                           (90, _link_faults(net, 0.3, 51,
                                             types=(T.GLOBAL,),
                                             vc_mode="baseline"))))
    cfg = SimConfig(warmup=60, measure=240, vc_mode="baseline",
                    route_mode="ugal", vcs_per_class=1)
    sim = Simulator(net, cfg, TR.uniform(net), faults=sch)
    r = sim.run(0.4)
    assert r.dropped_pkts == 0
    assert r.delivered_pkts > 0.8 * r.generated_pkts
