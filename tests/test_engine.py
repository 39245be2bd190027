"""Engine-level tests: pytree SimState, phase composition, batch purity of
the route function, and BatchedSweep equivalence with sequential runs."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import topology as T
from repro.core import traffic as TR
from repro.core import engine
from repro.core.engine import (BatchedSweep, Requests, SimState, SimStats,
                               build_lane, make_state, make_step)
from repro.core.engine import sweep as sweep_mod
from repro.core.routing import make_route_fn
from repro.core.simulator import SimConfig, Simulator


@pytest.fixture(scope="module")
def cgroup_net():
    p = T.SwitchlessParams(a=1, b=1, m=2, n=6, noc=2, g=1)
    return T.build_switchless(p, "engine-cgroup")


def test_simstate_is_pytree(cgroup_net):
    cfg = SimConfig()
    consts, _ = engine.build_consts(cgroup_net, cfg)
    state = make_state(cgroup_net, cfg, consts["NV"])
    leaves, treedef = jax.tree.flatten(state)
    assert all(isinstance(l, jax.Array) for l in leaves)
    rebuilt = jax.tree.unflatten(treedef, leaves)
    assert isinstance(rebuilt, SimState)
    assert isinstance(rebuilt.stats, SimStats)
    bumped = jax.tree.map(lambda x: x + 1, state)
    assert int(bumped.b_count.sum()) == state.b_count.size


def test_make_state_batch_axis(cgroup_net):
    cfg = SimConfig()
    consts, _ = engine.build_consts(cgroup_net, cfg)
    single = make_state(cgroup_net, cfg, consts["NV"])
    batched = make_state(cgroup_net, cfg, consts["NV"], batch=(3,))
    for a, b in zip(jax.tree.leaves(single), jax.tree.leaves(batched)):
        assert b.shape == (3,) + a.shape


def test_route_fn_batch_pure(cgroup_net):
    """vmapping the route function over a batch of packet vectors must equal
    looping it — the property BatchedSweep relies on."""
    route_fn = make_route_fn(cgroup_net, "baseline")
    rng = np.random.default_rng(0)
    V, Tn = cgroup_net.num_nodes, cgroup_net.num_terminals
    B, N = 4, 32
    cur = jnp.asarray(rng.integers(0, V, size=(B, N)))
    dest = jnp.asarray(rng.integers(0, Tn, size=(B, N)))
    mis = jnp.full((B, N), -1, dtype=jnp.int32)
    meta = jnp.zeros((B, N), dtype=jnp.int32)
    out_b, vc_b, meta_b = jax.vmap(route_fn)(cur, dest, mis, meta)
    for i in range(B):
        out, vc, m = route_fn(cur[i], dest[i], mis[i], meta[i])
        np.testing.assert_array_equal(np.asarray(out_b[i]), np.asarray(out))
        np.testing.assert_array_equal(np.asarray(vc_b[i]), np.asarray(vc))
        np.testing.assert_array_equal(np.asarray(meta_b[i]), np.asarray(m))


def test_step_grants_at_most_one_winner_per_channel(cgroup_net):
    cfg = SimConfig(warmup=10, measure=10, vcs_per_class=2, step_impl="jnp")
    consts, route_kernel = engine.build_consts(cgroup_net, cfg)
    inject = engine.make_inject_fn(cgroup_net, cfg, consts, TR.uniform(cgroup_net))
    arbitrate = engine.make_arbitrate_fn(cgroup_net, cfg, consts,
                                         route_kernel)
    fl = build_lane(cgroup_net, cfg)
    state = make_state(cgroup_net, cfg, consts["NV"])
    key = jax.random.PRNGKey(0)
    apply_moves = engine.make_apply_fn(cgroup_net, cfg, consts)
    for t in range(8):
        key, sub = jax.random.split(key)
        state = inject(state, t, sub, jnp.float32(0.9), fl)
        req, win, won_ch = arbitrate(state, t, fl)
        assert isinstance(req, Requests)
        # one winner per output channel
        outs = np.asarray(req.out)[np.asarray(win)]
        assert len(outs) == len(np.unique(outs))
        # winners must be valid requesters
        assert bool((np.asarray(win) <= np.asarray(req.valid)).all())
        # the dense grant mask agrees with the winner rows
        assert set(outs) == set(np.flatnonzero(np.asarray(won_ch)))
        state = apply_moves(state, req, win, won_ch, t)
        # occupancy never exceeds capacity, never goes negative
        bc = np.asarray(state.b_count)
        assert bc.min() >= 0 and bc.max() <= cfg.buf_pkts


def test_batched_sweep_matches_sequential(cgroup_net):
    """Acceptance: >= 6 rates x 2 seeds, throughput/latency within 2% of
    per-rate sequential Simulator.run, ONE jit compile for the whole sweep.

    The compile count comes from the module-level trace counter
    (`sweep.compile_counter`), not the private jit `_cache_size` API, so
    it cannot silently degrade to 0 on JAX versions without that API.
    The cycle count (101 + 397) is unique in the suite, so this call can
    never be a cache hit from an earlier test even without `clear_cache`.
    """
    cfg = SimConfig(warmup=101, measure=397, vcs_per_class=2)
    sim = Simulator(cgroup_net, cfg, TR.uniform(cgroup_net))
    rates = [0.2, 0.5, 0.9, 1.4, 2.0, 2.6]
    seeds = (0, 1)
    before = sweep_mod.compile_counter()
    grid = sim.sweep_grid(rates, seeds)
    assert grid.compile_count == 1
    assert sweep_mod.compile_counter() - before == 1
    # a second identical sweep is a cache hit: zero new compiles
    grid2 = sim.sweep_grid(rates, seeds)
    assert grid2.compile_count == 0
    for i, r in enumerate(rates):
        for j, s in enumerate(seeds):
            seq = sim.run(r, seed=s)
            bat = grid.result(i, j)
            assert bat.throughput_per_chip == pytest.approx(
                seq.throughput_per_chip, rel=0.02)
            assert bat.avg_latency == pytest.approx(seq.avg_latency, rel=0.02)
    # curve-level reductions
    sat = grid.saturation_throughput()
    assert sat == max(r.throughput_per_chip for r in grid.mean_over_seeds())


def test_sweep_rejects_overdriven_rate(cgroup_net):
    cfg = SimConfig(warmup=10, measure=10)
    sweep = BatchedSweep(cgroup_net, cfg, TR.uniform(cgroup_net))
    with pytest.raises(ValueError):
        sweep.run([100.0])


@pytest.fixture(scope="module")
def multi_wg_net():
    return T.build_switchless(
        T.SwitchlessParams(a=2, b=2, m=2, n=4, noc=2, g=5), "engine-multiwg")


def test_ugal_watch_pads_with_sentinel(multi_wg_net):
    """Unused sensor slots are -1 (masked), never channel id 0."""
    cfg = SimConfig(route_mode="ugal")
    watch = np.asarray(engine.build_ugal_watch(multi_wg_net, cfg))
    g = multi_wg_net.meta["g"]
    for w in range(g):
        for u in range(g):
            sens = watch[w, u]
            if w == u:
                assert (sens == -1).all()
                continue
            # the first slot is the watched global link itself
            assert sens[0] >= 0
            assert multi_wg_net.ch_type[sens[0]] == T.GLOBAL
            # once a slot is empty, the rest are empty too — and empty
            # means the -1 sentinel, not channel 0
            n = int((sens >= 0).sum())
            assert (sens[n:] == -1).all()


def test_ugal_congested_channel_zero_does_not_flip_nonmin(multi_wg_net):
    """Regression: the old 0-padded sensor table added channel 0's buffered
    occupancy to every entry with fewer than 5 feeders, so congestion on
    channel 0 could flip `take_nonmin` for flows that never touch it."""
    net = multi_wg_net
    cfg = SimConfig(route_mode="ugal", vcs_per_class=1)
    consts, _ = engine.build_consts(net, cfg)
    gen_mis = engine.make_misroute_fn(net, cfg, consts)
    fl = build_lane(net, cfg)
    g = net.meta["g"]
    T_ = net.num_terminals
    # craft an adversarial sensor table: the minimal-path entry towards
    # W-group `wd` has 4 empty (sentinel) slots, every other entry has 5
    # real but EMPTY sensor channels.  With 0-padding, congestion on
    # channel 0 inflates q_min by 4 x occ(0) while q_non stays 0, flipping
    # the comparison; with the sentinel fix both stay 0.
    wd = g - 1
    empty = net.first_eject - 1      # a channel no crafted sensor watches
    crafted = np.full((g, g, 5), empty, dtype=np.int64)
    crafted[:, wd, 1:] = -1
    fl = dict(fl, ugal_watch=jnp.asarray(crafted))
    # every source sends to terminal 0 of W-group wd
    tpw = net.meta["terms_per_wg"]
    dest = jnp.full((T_,), wd * tpw, dtype=jnp.int32)
    key = jax.random.PRNGKey(3)
    quiet = jnp.zeros((net.num_channels, consts["NV"]), jnp.int32)
    congested = quiet.at[0, :].set(cfg.buf_pkts)
    mis_quiet = np.asarray(gen_mis(key, dest, quiet, fl))
    mis_hot = np.asarray(gen_mis(key, dest, congested, fl))
    src_wg = np.asarray(consts["term_wg"])
    differ = src_wg != wd
    # all-empty sensors -> minimal everywhere, congested channel 0 or not
    assert (mis_quiet[differ] == -1).all()
    np.testing.assert_array_equal(mis_hot, mis_quiet)


def test_simulator_sweep_facade(cgroup_net):
    """Simulator.sweep keeps the historical list[SimResult] contract."""
    cfg = SimConfig(warmup=50, measure=200, vcs_per_class=2)
    sim = Simulator(cgroup_net, cfg, TR.uniform(cgroup_net))
    rates = [0.3, 0.6]
    out = sim.sweep(rates)
    assert len(out) == len(rates)
    assert [r.offered_per_chip for r in out] == rates
    assert all(r.throughput_per_chip > 0 for r in out)
