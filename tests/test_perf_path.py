"""Engine hot-path bookkeeping: the stranded-request gauge, the
compile-vs-run wall split, and the async lane dispatch."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import engine
from repro.core import topology as T
from repro.core import traffic as TR
from repro.core.engine import build_lane, make_state
from repro.core.engine.arbitrate import age_based_grant
from repro.core.engine.stats import accumulate
from repro.core.simulator import SimConfig, Simulator


@pytest.fixture(scope="module")
def updown_net():
    return T.build_switchless(
        T.SwitchlessParams(a=2, b=2, m=2, n=4, noc=2, g=3), "perf-path")


def test_stranded_gauge_counts_minus_one_requests(updown_net):
    """A head-of-line request parked on the -1 non-channel shows up in
    `SimStats.stranded` (and only there — it is never granted)."""
    net = updown_net
    cfg = SimConfig(vc_mode="updown", vcs_per_class=1, step_impl="jnp")
    consts, route_kernel = engine.build_consts(net, cfg)
    fl = build_lane(net, cfg)
    state = make_state(net, cfg, consts["NV"])
    state = state.replace(
        b_count=state.b_count.at[0, 0].set(1),
        b_pkt=state.b_pkt.at[0, 0, 0].set(
            jnp.asarray([5, 0, -1, 0, 0], jnp.int32)))
    crafted = dict(fl, ud_nh=jnp.full_like(fl["ud_nh"], -1))
    arbitrate = engine.make_arbitrate_fn(net, cfg, consts, route_kernel)
    req, win, _ = arbitrate(state, 0, crafted)
    stats = accumulate(state.stats, req, win, consts, 0)
    assert int(stats.stranded) == 1
    assert not bool(np.asarray(win)[0])
    # on the pristine tables the same packet routes fine: gauge reads 0
    req2, win2, _ = arbitrate(state, 0, fl)
    assert int(accumulate(state.stats, req2, win2, consts, 0).stranded) == 0


def test_stranded_surfaces_in_simresult(updown_net):
    """Pristine end-to-end runs report stranded_pkts == 0; the field is
    wired through finalize and the seed-averaged reductions."""
    net = updown_net
    cfg = SimConfig(warmup=29, measure=111, vc_mode="updown",
                    vcs_per_class=2)
    sim = Simulator(net, cfg, TR.uniform(net))
    grid = sim.sweep_grid([0.4], seeds=(0, 1))
    assert all(r.stranded_pkts == 0 for r in grid.flat())
    assert grid.mean_over_seeds()[0].stranded_pkts == 0


def test_sweep_wall_split_excludes_compile(updown_net):
    """First call reports compile_s > 0 separately from wall_s; the
    cache-hit re-run reports compile_s == 0.0 and compiles == 0."""
    net = updown_net
    cfg = SimConfig(warmup=23, measure=97, vc_mode="updown",
                    vcs_per_class=2)
    sim = Simulator(net, cfg, TR.uniform(net))
    first = sim.sweep_grid([0.3, 0.6], seeds=(0,))
    assert first.compile_count == 1
    assert first.compile_s > 0.0
    assert first.wall_s > 0.0
    again = sim.sweep_grid([0.3, 0.6], seeds=(0,))
    assert again.compile_count == 0
    assert again.compile_s == 0.0
    for a, b in zip(first.flat(), again.flat()):
        assert a.delivered_pkts == b.delivered_pkts
        assert a.avg_latency == b.avg_latency


def test_run_lanes_async_matches_sync(updown_net):
    """Async dispatch + finish returns the same lane results as the
    synchronous path (which is itself async + immediate finish)."""
    net = updown_net
    cfg = SimConfig(warmup=19, measure=83, vc_mode="updown",
                    vcs_per_class=2)
    sim = Simulator(net, cfg, TR.uniform(net))
    sweep = sim._batched
    lanes = [(0.3, 0, None), (0.5, 1, None)]
    sync = sweep.run_lanes(lanes)
    pend = sweep.run_lanes_async(lanes)
    out = pend.finish()
    assert [r.delivered_pkts for r in out.results] == \
        [r.delivered_pkts for r in sync.results]
    assert out.compile_count == 0      # second dispatch reuses the cache


def test_expand_vcs_single_gather_matches_loop(updown_net):
    """Regression for the vectorized VC expansion: the [N, vpc] gather
    equals the old per-VC loop (argmin ties break toward the lowest VC)."""
    from repro.core.engine.arbitrate import expand_vcs
    net = updown_net
    cfg = SimConfig(vc_mode="updown", vcs_per_class=3, step_impl="jnp")
    consts, route_kernel = engine.build_consts(net, cfg)
    fl = build_lane(net, cfg)
    inject = engine.make_inject_fn(net, cfg, consts, TR.uniform(net))
    apply_moves = engine.make_apply_fn(net, cfg, consts)
    state = make_state(net, cfg, consts["NV"])
    key = jax.random.PRNGKey(5)
    vpc = cfg.vcs_per_class
    for t in range(6):
        key, sub = jax.random.split(key)
        state = inject(state, t, sub, jnp.float32(0.7), fl)
        req = engine.arbitrate.gather_requests(state, consts, route_kernel,
                                               fl, t)
        got = expand_vcs(req, state, cfg)
        base = req.vc * vpc
        occs = jnp.stack(
            [state.b_count[req.out, base + i] for i in range(vpc)], axis=-1)
        want_vc = base + jnp.argmin(occs, axis=-1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(got.vc),
                                      np.asarray(want_vc))
        np.testing.assert_array_equal(np.asarray(got.ovc_count),
                                      np.asarray(jnp.min(occs, axis=-1)))
        win, won = age_based_grant(got, state, consts, cfg.buf_pkts,
                                   fl["ch_alive"])
        state = apply_moves(state, got, win, won, t)
