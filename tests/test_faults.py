"""Fault-injection subsystem: FaultSet semantics, sampler invariants,
deadlock freedom on degraded networks, fault-avoiding routing, and the
engine's fault-masked phase pipeline + batched failure-rate sweeps."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import engine
from repro.core import routing as R
from repro.core import topology as T
from repro.core import traffic as TR
from repro.core.engine import build_lane
from repro.core.simulator import SimConfig, Simulator


@pytest.fixture(scope="module")
def net():
    return T.build_switchless(
        T.SwitchlessParams(a=2, b=2, m=2, n=4, noc=2, g=5), "faults-net")


@pytest.fixture(scope="module")
def small_net():
    """Two C-groups x 4 W-groups, 128 terminals: engine-level fault tests
    compile in seconds here and exercise every channel type."""
    return T.build_switchless(
        T.SwitchlessParams(a=1, b=2, m=2, n=4, noc=2, g=4), "faults-small")


# --- FaultSet semantics ------------------------------------------------------

def test_dead_router_kills_incident_channels_and_terminal(net):
    v = 17
    f = T.FaultSet(dead_routers=(v,))
    alive = f.ch_alive(net)
    incident = (net.ch_src == v) | (net.ch_dst == v)
    assert (~alive[incident]).all()
    assert alive[~incident].all()
    ta = f.term_alive(net)
    assert not ta[v]                      # one terminal per router here
    assert ta.sum() == net.num_terminals - 1


def test_dead_channel_masks_only_itself(net):
    e = int(np.where(net.ch_type == T.GLOBAL)[0][0])
    f = T.FaultSet(dead_ch=(e,))
    alive = f.ch_alive(net)
    assert not alive[e] and alive.sum() == net.num_channels - 1
    assert f.frac_links_failed(net) > 0
    assert T.FaultSet().is_empty and f.union(T.FaultSet()) == f


def test_validate_rejects_unroutable_faults(net):
    # baseline vc_mode only tolerates GLOBAL faults
    mesh = int(np.where(net.ch_type == T.MESH)[0][0])
    rev = T.reverse_fabric_channel(net)
    with pytest.raises(ValueError):
        T.validate_faults(net, T.FaultSet(dead_ch=(mesh, int(rev[mesh]))),
                          vc_mode="baseline")
    # mesh faults must kill both directions
    with pytest.raises(ValueError):
        T.validate_faults(net, T.FaultSet(dead_ch=(mesh,)), "updown")
    # killing every global link of a W-group pair is unroutable
    t = net.tables
    ab = net.meta["ab"]
    chs = []
    npar = t["glob_route_cg"].shape[-1]
    for r in range(npar):
        cg = t["glob_route_cg"][0, 1, r]
        if cg >= 0:
            ch = t["ext_out"][cg, t["glob_route_port"][0, 1, r]]
            if ch >= 0:
                chs.append(int(ch))
    assert chs
    with pytest.raises(ValueError):
        T.validate_faults(net, T.FaultSet(dead_ch=tuple(chs)), "updown")


def test_samplers_produce_valid_fault_sets(net):
    rng = np.random.default_rng(5)
    fl = T.sample_link_faults(net, 0.1, rng)
    fr = T.sample_router_faults(net, 8, rng)
    fc = T.sample_cluster_faults(net, rng, num_clusters=2, radius=1)
    for f in (fl, fr, fc):
        info = T.validate_faults(net, f, "updown")
        assert info["alive_terminals"] > 0
    assert len(fl.dead_ch) > 0
    assert 0 < fl.frac_links_failed(net) <= 0.1 + 0.01
    assert len(fr.dead_routers) == 8
    assert len(fc.dead_routers) >= 3   # radius-1 cluster interior


def test_global_only_sampler_for_baseline(net):
    rng = np.random.default_rng(2)
    f = T.sample_link_faults(net, 0.25, rng, types=(T.GLOBAL,),
                             vc_mode="baseline")
    assert len(f.dead_ch) > 0
    assert (net.ch_type[list(f.dead_ch)] == T.GLOBAL).all()
    T.validate_faults(net, f, "baseline")


# --- deadlock freedom + fault avoidance on degraded networks -----------------

def _fault_for(net, vc_mode: str, seed: int) -> T.FaultSet:
    rng = np.random.default_rng(seed)
    if vc_mode == "baseline":
        return T.sample_link_faults(net, 0.3, rng, types=(T.GLOBAL,),
                                    vc_mode="baseline")
    # mix of a dead-router cluster and link failures composed on top of it
    # (the composed set is validated as a whole)
    cluster = T.sample_cluster_faults(net, rng, num_clusters=1, radius=1,
                                      vc_mode=vc_mode)
    return T.sample_link_faults(net, 0.08, rng, vc_mode=vc_mode,
                                base=cluster)


@pytest.mark.parametrize("mode", ["baseline", "updown", "updown_merged"])
@pytest.mark.parametrize("seed", [11, 23])
def test_deadlock_freedom_under_faults(net, mode, seed):
    """Acceptance: `assert_deadlock_free` on >= 2 distinct faulted networks
    per vc_mode; the traced paths must also avoid every dead channel."""
    faults = _fault_for(net, mode, seed)
    assert not faults.is_empty
    rng = np.random.default_rng(seed)
    edges = R.assert_deadlock_free(net, mode, nonminimal=True, rng=rng,
                                   n_pairs=4000, faults=faults)
    assert edges > 0


def test_vc_bounds_hold_under_faults(net):
    """The VC budget of each scheme survives degradation: rebuilt tables
    never push a packet past its class bound."""
    rng = np.random.default_rng(99)
    f = _fault_for(net, "updown", 99)
    alive_t = np.flatnonzero(f.term_alive(net))
    s = alive_t[rng.integers(0, len(alive_t), 3000)]
    d = alive_t[rng.integers(0, len(alive_t), 3000)]
    keep = s != d
    s, d = s[keep], d[keep]
    g = net.meta["g"]
    wg = net.tables["node_wg"]
    wg_s, wg_d = wg[net.term_node[s]], wg[net.term_node[d]]
    mis = rng.integers(0, g, size=len(s))
    mis = np.where((mis == wg_s) | (mis == wg_d), -1, mis)
    for mode, bound in [("updown", 3), ("updown_merged", 2)]:
        rf = R.make_route_fn(net, mode, f)
        m = mis if mode != "updown_merged" else np.where(mis < wg_d, mis, -1)
        _, vcs, _ = R.trace_paths(net, rf, s, d, m)
        assert int(vcs.max()) + 1 <= bound, mode


def test_faulted_updown_tables_avoid_dead_routers(net):
    f = _fault_for(net, "updown", 11)
    rank, nh = R.build_updown_tables(net, faults=f)
    g = net.meta["g"]
    NW = net.meta["ab"] * net.meta["nodes_per_cg"]
    node_alive = f.node_alive(net).reshape(g, NW)
    for wg in range(g):
        dead = np.where(~node_alive[wg])[0]
        alive = np.where(node_alive[wg])[0]
        if len(dead) == 0:
            continue
        # no alive->alive next hop ever routes through a dead router
        sub = nh[wg][np.ix_(alive, alive)]
        assert not np.isin(sub, dead).any()
    # pristine W-groups keep the pristine tables
    rank0, nh0 = R.build_updown_tables(net)
    untouched = [wg for wg in range(g)
                 if node_alive[wg].all()
                 and not np.isin(np.asarray(f.dead_ch),
                                 np.where(net.ch_src // NW == wg)[0]).any()]
    for wg in untouched:
        np.testing.assert_array_equal(nh[wg], nh0[wg])


def test_global_repick_spreads_over_alive_links(net):
    """Killing one parallel global link must redirect its flows onto the
    surviving parallel links of the same W-group pair."""
    wired = T._wired_global_links(net)
    w, u = 0, 1
    links = wired[w, u][wired[w, u] >= 0]
    if len(links) < 2:
        pytest.skip("net has no parallel global links for this pair")
    f = T.FaultSet(dead_ch=(int(links[0]),))
    fl = R.route_tables(net, "baseline", f)
    cnt = np.asarray(fl["glob_cnt"])
    idx = np.asarray(fl["glob_idx"])
    assert cnt[w, u] == len(links) - 1
    assert 0 not in idx[w, u, :cnt[w, u]]


# --- engine under faults -----------------------------------------------------

def test_engine_never_grants_dead_channel(small_net):
    """Phase-level invariant on a degraded network: no granted movement
    targets a dead channel, buffers stay in range."""
    net = small_net
    faults = _fault_for(net, "updown", 23)
    cfg = SimConfig(warmup=10, measure=10, vc_mode="updown",
                    vcs_per_class=2, step_impl="jnp")
    consts, route_kernel = engine.build_consts(net, cfg)
    inject = engine.make_inject_fn(net, cfg, consts, TR.uniform(net))
    arbitrate = engine.make_arbitrate_fn(net, cfg, consts, route_kernel)
    apply_moves = engine.make_apply_fn(net, cfg, consts)
    fl = build_lane(net, cfg, faults)
    alive = np.asarray(fl["ch_alive"])
    dead_terms = ~np.asarray(fl["term_alive"])
    state = engine.make_state(net, cfg, consts["NV"])
    key = jax.random.PRNGKey(1)
    granted = 0
    for t in range(20):
        key, sub = jax.random.split(key)
        state = inject(state, t, sub, jnp.float32(0.9), fl)
        req, win, won_ch = arbitrate(state, t, fl)
        w = np.asarray(win)
        granted += int(w.sum())
        assert alive[np.asarray(req.out)[w]].all()
        assert not np.asarray(won_ch)[~alive].any()
        state = apply_moves(state, req, win, won_ch, t)
        bc = np.asarray(state.b_count)
        assert bc.min() >= 0 and bc.max() <= cfg.buf_pkts
    assert granted > 0
    # dead terminals never accumulate source-queue packets
    assert (np.asarray(state.s_count)[dead_terms] == 0).all()


def test_faulted_lane_delivers_at_low_load(small_net):
    """A faulted BatchedSweep lane delivers (essentially) every generated
    packet at low load: nothing is routed into a dead channel and lost."""
    net = small_net
    faults = _fault_for(net, "updown", 11)
    cfg = SimConfig(warmup=200, measure=1200, vc_mode="updown",
                    vcs_per_class=2)
    sim = Simulator(net, cfg, TR.uniform(net), faults=faults)
    r = sim.run(0.1)
    assert r.dropped_pkts == 0
    assert r.generated_pkts > 200
    # in-flight slack: a packet generated near the end of the window is
    # still traversing the network when measurement stops
    assert r.delivered_pkts >= 0.9 * r.generated_pkts
    assert r.throughput_per_chip == pytest.approx(0.1, rel=0.15)


def test_batched_fault_grid_matches_sequential(small_net):
    """One batched failure-rate x seed sweep == per-lane sequential runs,
    with exactly one compile for the whole grid.  The sequential side
    reuses ONE compiled Simulator and swaps fault sets per run (fault data
    is a traced argument, not part of the compiled step)."""
    from repro.core.engine import sweep as sweep_mod
    net = small_net
    cfg = SimConfig(warmup=103, measure=397, vc_mode="updown",
                    vcs_per_class=2)
    pattern = TR.uniform(net)
    seeds = (0, 1)
    fault_grid = [
        [T.FaultSet()] * len(seeds),
        [_fault_for(net, "updown", 11)] * len(seeds),
        [_fault_for(net, "updown", 23), _fault_for(net, "updown", 37)],
    ]
    sim = Simulator(net, cfg, pattern)
    before = sweep_mod.compile_counter()
    grid = sim.sweep_faults(0.3, fault_grid, seeds=seeds)
    assert grid.compile_count == 1
    assert sweep_mod.compile_counter() - before == 1
    assert grid.fault_fracs[0] == 0.0
    assert grid.fault_fracs[1] > 0 and grid.fault_fracs[2] > 0
    for i, row in enumerate(fault_grid):
        for j, (f, s) in enumerate(zip(row, seeds)):
            seq = sim.run(0.3, seed=s, faults=None if f.is_empty else f)
            bat = grid.result(i, j)
            assert bat.delivered_pkts == seq.delivered_pkts
            assert bat.generated_pkts == seq.generated_pkts
            assert bat.throughput_per_chip == pytest.approx(
                seq.throughput_per_chip, rel=1e-6)
