"""Fused cycle step (`step_impl="fused"`) vs the jnp oracle: bit parity.

The fused step reorders the whole cycle around per-channel winner
arbitration (route-once-per-hop caching, one segment-min grant, gather
pops — see repro/core/engine/fused.py) but must stay BIT-IDENTICAL to
the classic phase pipeline on every counter of every lane: same grants,
same pops, same stats, exact int and float equality.  Pinned here on
live engine runs across the three vc_modes, cold fault sets, and warm
`FaultSchedule`s (scheduled lanes exercise the per-cycle routing
fallback, pristine ones the cached fast path).

The `grant_impl="pallas"` variant routes the fused grant through the
`repro.kernels.netsim.cycle_core` Pallas kernel (interpret mode on CPU)
and must also be bit-identical; its standalone contract against the jnp
reduction is pinned in test_netsim_kernel.py-style form below.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import topology as T
from repro.core import traffic as TR
from repro.core.simulator import SimConfig, Simulator
from repro.core.topology import FaultSchedule, FaultSet

NET = T.build_switchless(
    T.SwitchlessParams(a=1, b=1, m=2, n=6, noc=2, g=3), "fused-par")
GLOB = np.where(np.asarray(NET.ch_type) == T.GLOBAL)[0]
WARMUP, MEASURE = 40, 140


def _faults(vc_mode):
    if vc_mode == "baseline":
        return FaultSet(dead_ch=frozenset(int(c) for c in GLOB[:2]))
    return FaultSet(dead_routers=frozenset({5, 11}))


def _schedule(vc_mode):
    return FaultSchedule(((0, FaultSet()), (60, _faults(vc_mode))))


def _rows(cfg, faults):
    sim = Simulator(NET, cfg, TR.uniform(NET), faults=faults)
    return [(r.delivered_pkts, r.generated_pkts, r.dropped_pkts,
             r.avg_latency, r.throughput_per_chip, r.stranded_pkts,
             tuple(sorted(r.hops_by_type.items())))
            for r in sim.sweep([0.4, 1.2], seeds=(0, 1))]


CASES = [("baseline", "min", 2), ("baseline", "ugal", 1),
         ("updown", "val", 2), ("updown_merged", "min", 2)]


@pytest.mark.parametrize("vc_mode,route_mode,vpc", CASES)
@pytest.mark.parametrize("fkind", ["pristine", "cold", "warm"])
def test_fused_step_bit_identical(vc_mode, route_mode, vpc, fkind):
    faults = (None if fkind == "pristine"
              else _faults(vc_mode) if fkind == "cold"
              else _schedule(vc_mode))
    rows = {}
    for impl in ("jnp", "fused"):
        cfg = SimConfig(warmup=WARMUP, measure=MEASURE, vc_mode=vc_mode,
                        route_mode=route_mode, vcs_per_class=vpc,
                        step_impl=impl)
        rows[impl] = _rows(cfg, faults)
    assert rows["fused"] == rows["jnp"]


@pytest.mark.parametrize("fkind", ["pristine", "cold"])
def test_fused_pallas_grant_bit_identical(fkind):
    """grant_impl="pallas" inside the fused step (interpret mode on CPU)
    matches the jnp fused path exactly on a live engine run."""
    faults = None if fkind == "pristine" else _faults("baseline")
    rows = {}
    for gi in ("jnp", "pallas"):
        cfg = SimConfig(warmup=WARMUP, measure=MEASURE,
                        vc_mode="baseline", route_mode="min",
                        vcs_per_class=2, step_impl="fused",
                        grant_impl=gi)
        rows[gi] = _rows(cfg, faults)
    assert rows["pallas"] == rows["jnp"]


def test_cycle_core_matches_jnp_reduction():
    """The standalone kernel contract: cycle_core == the fused step's
    `_grant` segment-min (winner mask, winner row ids, pop mask) on
    random request tables, including all-ineligible channels."""
    from repro.core.engine.fused import _grant
    from repro.kernels.netsim import cycle_core

    rng = np.random.default_rng(7)
    for N, E in [(300, 37), (1024, 128), (77, 5)]:
        out = jnp.asarray(rng.integers(-1, E, N), jnp.int32)
        itime = jnp.asarray(rng.integers(0, 900, N), jnp.int32)
        ok = jnp.asarray(rng.random(N) < 0.6) & (out >= 0)
        ch_ok = jnp.asarray(rng.random(E) < 0.8)
        r2 = 1 << int(N - 1).bit_length()
        prio = jnp.arange(N, dtype=jnp.int32)
        won_ref, wprio_ref = _grant(ok, out, itime, prio, ch_ok, E, r2,
                                    True)
        won, wprio, win = cycle_core(out, itime, ok, ch_ok, r2=r2)
        assert (np.asarray(won) == np.asarray(won_ref)).all()
        assert (np.asarray(wprio) == np.asarray(wprio_ref)).all()
        # the emitted pop mask is the winner rows exactly
        wp = np.where(np.asarray(won_ref), np.asarray(wprio_ref), -1)
        exp = np.zeros(N, bool)
        exp[wp[wp >= 0]] = True
        assert (np.asarray(win) == exp).all()


def test_cycle_core_compiled_unsupported_on_cpu():
    """Non-interpret Pallas lowering is a TPU feature; on CPU the
    compiled attempt must fail loudly (bench_perf records it as
    `supported: false`), never silently produce wrong grants."""
    from repro.kernels.netsim import cycle_core

    if jax.default_backend() == "tpu":
        pytest.skip("compiled path is supported on TPU")
    out = jnp.zeros(16, jnp.int32)
    ok = jnp.ones(16, bool)
    ch_ok = jnp.ones(4, bool)
    with pytest.raises(Exception):
        jax.block_until_ready(jax.jit(
            lambda o, t, k, c: cycle_core(o, t, k, c, r2=32,
                                          interpret=False)
        )(out, out, ok, ch_ok))


def test_step_impl_spec_roundtrip():
    """RoutingSpec carries step_impl through validation, SimConfig
    lowering, and JSON round-trip."""
    from repro.exp.spec import ExperimentSpec, RoutingSpec, SweepAxes

    r = RoutingSpec(step_impl="fused")
    axes = SweepAxes(rates=(0.5,), warmup=10, measure=20)
    assert r.to_simconfig(axes).step_impl == "fused"
    assert RoutingSpec.from_dict(r.to_dict()) == r
    with pytest.raises(ValueError):
        RoutingSpec(step_impl="warp")
    spec = ExperimentSpec(
        name="x",
        topologies={"kind": "switchless",
                    "params": dict(a=1, b=1, m=2, n=6, noc=2, g=1)},
        traffics={"pattern": "uniform"}, routings=r, axes=axes)
    assert ExperimentSpec.from_dict(spec.to_dict()) == spec


# ---------------------------------------------------------------------------
# the fused step is the default step form
# ---------------------------------------------------------------------------

SESSION_LANES = [(0.6, 1, None), (1.2, 2, None)]


def _windowed(cfg, lanes, window=10, windows=2):
    """A `start_lanes` session filled to `cfg.warmup`, then advanced
    `windows` more windows: (session, host state)."""
    from repro.core.engine import BatchedSweep
    sess = BatchedSweep(NET, cfg, TR.uniform(NET)).start_lanes(
        lanes, window=window)
    while sess.cycle < cfg.warmup:
        sess.advance()
    for _ in range(windows):
        sess.advance()
    return sess, jax.tree.map(np.asarray, sess.state)


def test_default_step_is_fused_with_cached_route_records():
    from repro.core.engine import make_state, make_step
    from repro.core.engine.state import NUM_FUSED_FIELDS
    from repro.exp.spec import RoutingSpec, SweepAxes

    axes = SweepAxes(rates=(0.5,), warmup=10, measure=20)
    for cfg in (SimConfig(), RoutingSpec().to_simconfig(axes)):
        assert cfg.step_impl == "fused"
        step, consts = make_step(NET, cfg, TR.uniform(NET))
        assert step.__module__ == "repro.core.engine.fused"
        state = make_state(NET, cfg, consts["NV"])
        assert state.b_pkt.shape[-1] == NUM_FUSED_FIELDS


def test_default_session_matches_oracle_session():
    """A default-config windowed session (fill, then two windows, two
    lanes) equals the same session on the named oracle in every counter
    and in the in-flight state."""
    cfg = SimConfig(warmup=30, measure=200)
    fused_sess, got = _windowed(cfg, SESSION_LANES)
    oracle_sess, want = _windowed(
        SimConfig(warmup=30, measure=200, step_impl="jnp"), SESSION_LANES)
    assert (fused_sess.step_impl, oracle_sess.step_impl) == ("fused", "jnp")
    assert fused_sess.cycle == oracle_sess.cycle == 50
    for name in got.stats.__dataclass_fields__:
        np.testing.assert_array_equal(getattr(got.stats, name),
                                      getattr(want.stats, name), name)
    assert int(got.stats.delivered.sum()) > 0
    for name in ("b_count", "s_count", "ch_busy"):
        np.testing.assert_array_equal(getattr(got, name),
                                      getattr(want, name), name)


def _combined_limit(cfg):
    """The largest cycle budget whose packed grant key fits int32 on
    NET: ``cycles * R2 + R2 - 1 < 2**31 - 1``."""
    from repro.core.engine.fused import compact_rows
    r2 = 1 << (compact_rows(NET, cfg) - 1).bit_length()
    return 2**31 // r2 - 2


@pytest.mark.parametrize("over", [0, 1])
def test_session_reports_grant_form_at_the_overflow_boundary(over):
    from repro.core.engine import BatchedSweep
    limit = _combined_limit(SimConfig())
    cfg = SimConfig(warmup=10, measure=limit - 10 + over)
    sess = BatchedSweep(NET, cfg, TR.uniform(NET)).start_lanes(
        SESSION_LANES, window=10)
    assert sess.step_impl == "fused"
    assert sess.route_cached
    assert sess.grant_form == ("two_pass" if over else "combined")


@pytest.mark.parametrize("impl", ["fused", "compact", "jnp"])
def test_session_and_sweep_report_route_cache(impl):
    """`route_cached` is false on the oracle and on a warm-schedule lane,
    which routes every cycle; `SweepResult` reports the same."""
    from repro.core.engine import BatchedSweep
    cfg = SimConfig(warmup=10, measure=20, step_impl=impl)
    sweep = BatchedSweep(NET, cfg, TR.uniform(NET))
    cold = sweep.start_lanes(SESSION_LANES, window=10)
    warm = sweep.start_lanes([(0.6, 1, _schedule("baseline"))], window=10)
    assert (cold.step_impl, warm.step_impl) == (impl, impl)
    assert cold.route_cached == (impl != "jnp")
    assert not warm.route_cached
    grid = sweep.run([0.6], seeds=(1,))
    assert (grid.step_impl, grid.route_cached) == (impl, impl != "jnp")
    assert grid.grant_form == cold.grant_form


@pytest.mark.parametrize("impl", ["fused", "jnp"])
@pytest.mark.parametrize("warm", [False, True])
def test_experiment_grid_reports_step_form(impl, warm):
    """`GridResult` and the `SweepResult` it hands out carry the step form
    the grid ran and whether its route cache engaged."""
    from repro.exp import run_experiment
    from repro.exp.spec import (ExperimentSpec, FaultSpec, RoutingSpec,
                                SweepAxes)
    faults = (FaultSpec(kind="routers", num=1, onsets=(15,)) if warm
              else FaultSpec())
    spec = ExperimentSpec(
        name="step-form",
        topologies={"kind": "switchless",
                    "params": dict(a=1, b=1, m=2, n=6, noc=2, g=3)},
        traffics={"pattern": "uniform"},
        routings=RoutingSpec(vc_mode="updown", step_impl=impl),
        axes=SweepAxes(rates=(0.6,), faults=(faults,), warmup=10,
                       measure=20))
    grid = run_experiment(spec).grids[0]
    cached = impl != "jnp" and not warm
    assert (grid.step_impl, grid.route_cached) == (impl, cached)
    res = grid.sweep_result()
    assert (res.step_impl, res.route_cached) == (impl, cached)
