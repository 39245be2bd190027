"""Arbitration phase: gather per-(channel, VC) and per-source-queue
requesters, route them, expand deadlock class to physical VC, apply
credit/busy constraints, and grant one winner per output channel by
age-based (oldest-first) segment-min arbitration.

The request vector is ordered [E_req*NV buffer heads, then T source queues]
(E_req = first eject channel id); `win[:E_req*NV]` / `win[E_req*NV:]` is the
contract the apply phase relies on.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp

from ..spans import phase
from ..topology import EJECT, Network
from .state import (F_DEST, F_ITIME, F_META, F_MIS, F_READY, INF32,
                    SimState)

# the valid `cfg.grant_impl` values — the single source of truth
# (SimConfig and exp.RoutingSpec validate against this)
GRANT_IMPLS = ("jnp", "pallas")


@jax.tree_util.register_dataclass
@dataclass
class Requests:
    """One row per potential packet movement this cycle ([E_req*NV + T])."""

    dest: jax.Array       # destination terminal
    itime: jax.Array      # generation cycle (arbitration age key)
    mis: jax.Array        # misroute W-group (-1 = minimal)
    meta: jax.Array       # routing meta AFTER the requested hop
    out: jax.Array        # requested output channel
    vc: jax.Array         # requested downstream physical VC
    valid: jax.Array      # bool: the row holds a forwardable packet
    # gathered per-row properties of the requested output channel
    # (one packed ch_tbl gather; reused by grant, stats, and apply)
    otype: jax.Array      # channel type of `out`
    odst_wg: jax.Array    # W-group of the downstream node of `out`
    olat: jax.Array       # pipeline latency of `out`
    ovc_count: jax.Array  # occupancy of the requested (out, vc) buffer
                          # (set by expand_vcs; feeds credit check + push slot)

    def replace(self, **kw) -> "Requests":
        return replace(self, **kw)


def gather_requests(state: SimState, consts, route_kernel, fl,
                    t) -> Requests:
    """Head-of-line packets of every non-eject (channel, VC) buffer + source
    queue.  Eject channels are the trailing id block and never hold packets,
    so restricting the grid to [:E_req] is a free slice that shrinks every
    downstream row-wise op.  `fl` carries the lane's fault-dependent
    routing tables into the route kernel."""
    NV, T, ER = consts["NV"], consts["T"], consts["E_req"]
    bh = state.b_head[:ER]                         # [E_req, NV]
    e_idx = jnp.arange(ER)[:, None].repeat(NV, 1)
    v_idx = jnp.arange(NV)[None, :].repeat(ER, 0)
    # ONE gather pulls the whole packed head record per (channel, VC)
    head_pkt = state.b_pkt[(e_idx, v_idx, bh)].reshape(ER * NV, -1)
    r_dest = head_pkt[:, F_DEST]
    r_itime = head_pkt[:, F_ITIME]
    r_mis = head_pkt[:, F_MIS]
    r_meta = head_pkt[:, F_META]
    r_ready = head_pkt[:, F_READY]
    r_valid = ((state.b_count[:ER] > 0).reshape(-1) & (r_ready <= t))
    cur_node = consts["ch_dst"][e_idx.reshape(-1)]
    out_ch, req_vc, new_meta = route_kernel(fl, cur_node, r_dest, r_mis,
                                            r_meta)

    # source-queue requesters: fixed out channel (the injection link)
    sq_pkt = state.s_pkt[(jnp.arange(T), state.s_head)]   # [T, 3]
    zeros_t = jnp.zeros(T, jnp.int32)
    out = jnp.concatenate([out_ch, consts["inject_ch"]]).astype(jnp.int32)
    otbl = consts["ch_tbl"][out]                          # [N, 3]
    return Requests(
        dest=jnp.concatenate([r_dest, sq_pkt[:, F_DEST]]),
        itime=jnp.concatenate([r_itime, sq_pkt[:, F_ITIME]]),
        mis=jnp.concatenate([r_mis, sq_pkt[:, F_MIS]]),
        meta=jnp.concatenate([new_meta, zeros_t]),
        out=out,
        vc=jnp.concatenate([req_vc, zeros_t]).astype(jnp.int32),
        valid=jnp.concatenate([r_valid, state.s_count > 0]),
        otype=otbl[:, 0], odst_wg=otbl[:, 1], olat=otbl[:, 2],
        ovc_count=jnp.zeros_like(out))


def expand_vcs(req: Requests, state: SimState, cfg) -> Requests:
    """Deadlock class -> physical VC: least-occupied VC of the class.

    Also records the chosen buffer's occupancy (`ovc_count`) so the credit
    check and the push-slot computation read it densely instead of
    re-gathering b_count.  The class's `vpc` occupancies come back in ONE
    `[N, vpc]` gather (gathers lower to per-row loops on CPU, so one row
    of `vpc` values beats `vpc` rows of one — same reasoning as the
    packed `b_pkt` record)."""
    vpc = cfg.vcs_per_class
    if vpc <= 1:
        return req.replace(ovc_count=state.b_count[req.out, req.vc])
    base = req.vc * vpc
    vc_idx = base[:, None] + jnp.arange(vpc, dtype=jnp.int32)[None, :]
    occs = state.b_count[req.out[:, None], vc_idx]          # [N, vpc]
    return req.replace(
        vc=base + jnp.argmin(occs, axis=-1).astype(jnp.int32),
        ovc_count=jnp.min(occs, axis=-1))


def age_based_grant(req: Requests, state: SimState, consts, buf_pkts: int,
                    ch_alive=None):
    """One winner per output channel, oldest `itime` first (ids break ties).

    Returns (win, won_ch): the boolean winner mask aligned with the request
    vector, and the dense per-channel mask of output channels that granted a
    winner this cycle (a channel with any eligible requester always grants
    exactly one — `m1 != INF` — which gives apply the serialization update
    without another scatter).

    `ch_alive` (the lane's fault mask) makes dead channels ungrantable —
    fault-aware routing never requests one, so this is defence in depth
    that also covers hand-built states in tests.  A request for the -1
    non-channel (a packet STRANDED by a warm fault: its router or target
    died mid-run, see the updown kernel) is likewise never granted — the
    packet stays buffered and accounted in-flight.
    """
    E = consts["E"]
    is_ej = req.otype == EJECT
    credit = req.ovc_count < buf_pkts
    ok = req.valid & (req.out >= 0) \
        & (state.ch_busy[req.out] == 0) & (credit | is_ej)
    if ch_alive is not None:
        ok = ok & ch_alive[req.out]

    seg = jnp.where(ok, req.out, E)
    key1 = jnp.where(ok, req.itime, INF32)
    m1 = jax.ops.segment_min(key1, seg, num_segments=E + 1)
    tie = ok & (req.itime == m1[req.out])
    ridx = jnp.arange(req.out.shape[0], dtype=jnp.int32)
    key2 = jnp.where(tie, ridx, INF32)
    m2 = jax.ops.segment_min(key2, seg, num_segments=E + 1)
    win = tie & (ridx == m2[req.out])
    won_ch = m1[:E] != INF32
    return win, won_ch


def make_arbitrate_fn(net: Network, cfg, consts, route_kernel):
    """Returns arbitrate(state, t, fl) -> (Requests, win_mask, won_ch_mask).

    `cfg.grant_impl` selects the grant implementation: "jnp" (default) is
    `age_based_grant` above — the `jax.ops.segment_min` path that doubles
    as the oracle; "pallas" is the fused netsim kernel
    (`repro.kernels.netsim`), bit-identical by the parity tests and the
    TPU-ready fast path (interpret mode on CPU)."""
    impl = getattr(cfg, "grant_impl", "jnp")
    if impl == "pallas":
        from ...kernels.netsim.ops import grant as netsim_grant

        def grant_fn(req, state, ch_alive):
            return netsim_grant(
                req.out, req.itime, req.valid, req.ovc_count,
                req.otype == EJECT, state.ch_busy, ch_alive,
                buf_pkts=cfg.buf_pkts)
    elif impl == "jnp":
        def grant_fn(req, state, ch_alive):
            return age_based_grant(req, state, consts, cfg.buf_pkts,
                                   ch_alive)
    else:
        raise ValueError(f"unknown grant_impl {impl!r}; "
                         f"valid: {GRANT_IMPLS}")

    def arbitrate(state, t, fl):
        with phase("route"):
            req = gather_requests(state, consts, route_kernel, fl, t)
            req = expand_vcs(req, state, cfg)
        with phase("grant"):
            win, won_ch = grant_fn(req, state, fl["ch_alive"])
        return req, win, won_ch

    return arbitrate
