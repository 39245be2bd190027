"""One simulated cycle, wired from the phase modules:

    inject -> arbitrate (route + VC expansion + grant) -> apply -> stats

`make_step` returns a pure function `step(state, (t, key, rate_pkt, fl))`
whose carry is the pytree `SimState`; `fl` is the lane's fault data
(`state.build_lane`: alive masks + fault-dependent routing tables) — an
explicit traced argument rather than a closure constant, so the batched
sweep can vmap one compiled step over lanes with different fault sets.
`run_scan` advances one lane `cycles` times inside one jitted `lax.scan`,
donating the state so buffers are reused in place.  Both are
`vmap`-compatible over a leading batch axis (see `sweep.py`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..spans import phase, span
from ..topology import Network
from ..traffic import as_pattern
from .apply import make_apply_fn
from .arbitrate import make_arbitrate_fn
from .inject import make_inject_fn
from .state import build_consts, resolve_epoch, resolve_reap_age
from .stats import accumulate, reap_mask, track_occ, zero_stats

# the valid `cfg.step_impl` values — the single source of truth
# (SimConfig and exp.RoutingSpec validate against this): "jnp" is the
# phase pipeline below (the oracle), "fused" the per-channel-winner
# restructuring in `fused.py` (bit-identical; the paper-scale fast
# path), "compact" the occupancy-compacted fused step (also fused.py:
# live rows compacted into a capacity-C active set before arbitration,
# bit-identical with a post-run capacity certificate — see
# `fused.make_compact_step` and the sweep's escalation ladder)
STEP_IMPLS = ("jnp", "fused", "compact")


@span("repro.build.step")
def make_step(net: Network, cfg, pattern, inject_mask=None):
    """Returns (step, consts);
    step(state, (t, key, rate_pkt, fl)) -> (state, None).

    `pattern` may be a bare sampler or a normalized `TrafficPattern`
    pair; a pattern-borne inject mask (e.g. hotspot's hot-source mask)
    composes with the explicit `inject_mask` argument.

    When `fl` is epoch-stacked (a `FaultSchedule` lane, see
    `state.build_lane`), the step first resolves the traced epoch index
    from `t` and hands the phases that epoch's alive masks and routing
    tables — mid-run link death is the epoch index advancing, and every
    in-flight packet is re-routed on the surviving subgraph from the next
    cycle on (buffered packets are preserved, never dropped)."""
    impl = getattr(cfg, "step_impl", "jnp")
    if impl == "fused":
        from .fused import make_fused_step
        return make_fused_step(net, cfg, pattern, inject_mask)
    if impl == "compact":
        from .fused import make_compact_step
        return make_compact_step(net, cfg, pattern, inject_mask)
    if impl != "jnp":
        raise ValueError(f"unknown step_impl {impl!r}; "
                         f"valid: {STEP_IMPLS}")
    pattern, inject_mask = as_pattern(pattern, inject_mask)
    consts, route_kernel = build_consts(net, cfg)
    inject = make_inject_fn(net, cfg, consts, pattern, inject_mask)
    arbitrate = make_arbitrate_fn(net, cfg, consts, route_kernel)
    apply_moves = make_apply_fn(net, cfg, consts)
    # router-death reaper (trace-time: 0 compiles the pre-reaper step)
    reap_age = resolve_reap_age(cfg)

    def step(state, t_key_rate_fl):
        t, key, rate_pkt, fl = t_key_rate_fl
        fl = resolve_epoch(fl, t)
        with phase("inject"):
            state = inject(state, t, key, rate_pkt, fl)
        with phase("stats"):
            stats = track_occ(state.stats, state)
        req, win, won_ch = arbitrate(state, t, fl)   # route, grant
        alive = fl["ch_alive"]
        with phase("stats"):
            reap = (reap_mask(req, t, reap_age, alive)
                    if reap_age else None)
            stats = accumulate(stats, req, win, consts, t, reap=reap,
                               ch_alive=alive if reap_age else None)
        with phase("apply"):
            state = apply_moves(state, req, win, won_ch, t, reap=reap)
        return state.replace(stats=stats), None

    return step, consts


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3,))
def run_scan(step, cycles, reset_at, state0, rate_pkt, key, fl):
    """Advance one lane `cycles` steps; stats are zeroed after warmup."""

    def body(carry, t):
        state, key = carry
        key, sub = jax.random.split(key)
        state, _ = step(state, (t, sub, rate_pkt, fl))
        with phase("stats"):
            st = jax.lax.cond(t == reset_at, zero_stats, lambda s: s,
                              state.stats)
        return (state.replace(stats=st), key), None

    (state, _), _ = jax.lax.scan(body, (state0, key), jnp.arange(cycles))
    return state
