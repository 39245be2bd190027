"""Simulation state (pytree) and static model constants.

`SimState` is the single carry of the cycle loop: every field is a
fixed-shape jnp array, so the whole state is a JAX pytree that can be
`lax.scan`-carried, `jax.vmap`-batched over a (rate x seed) sweep axis, and
donated across scan steps to keep memory flat.  An optional leading batch
axis on every array is the contract the phase functions obey: they never
reshape across axis 0, so `vmap` over axis 0 is always legal.

`build_consts` packages the static (per-network, per-config) arrays the
phases close over; these carry no batch axis and are captured by the jitted
step, not threaded through the carry.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import numpy as np
import jax
import jax.numpy as jnp

from ... import env_int
from ..topology import (NUM_CH_TYPES, FaultSchedule, FaultSet, Network,
                        glob_pair_alive, wg_channel_alive_frac)
from ..routing import make_route_kernel, num_vcs, route_tables
from ..spans import span

INF32 = jnp.int32(2**31 - 1)

# payload-field indices of the packed per-packet record in `SimState.b_pkt`.
# Packing all five fields into one trailing axis turns the five head gathers
# and five push scatters of the monolithic simulator into ONE gather and ONE
# scatter per cycle — scatter/gather lower to per-row loops on CPU, so row
# count, not element count, is what the hot loop pays for.
F_DEST, F_ITIME, F_MIS, F_META, F_READY = range(5)
NUM_FIELDS = 5
NUM_SRC_FIELDS = 3      # source-queue records pack (dest, itime, mis)

# the fused step (`cfg.step_impl="fused"`) extends the record with the
# CACHED next-hop route decision: a packet's route out of a channel is a
# pure function of (the packet, the channel, the lane's fault epoch), so
# the fused step evaluates it ONCE when the packet is pushed (E winner
# rows) instead of for every head row every cycle, and stores the output
# channel, requested VC class, and next routing meta alongside the
# payload.  Epoch-scheduled (warm-fault) lanes can't cache — the epoch
# in effect at head time isn't known at push time — so the fused step
# falls back to per-cycle routing there and these fields stay zero.
# The occupancy-compacted step (`step_impl="compact"`) carries the same
# cached tail.
F_OUT, F_CLS, F_META2 = 5, 6, 7
NUM_FUSED_FIELDS = 8

# step impls whose records carry the cached-route tail
CACHED_ROUTE_IMPLS = ("fused", "compact")


def resolve_reap_age(cfg) -> int:
    """Effective router-death reaper park age for this run (cycles).

    `cfg.reap_age` wins when nonzero; otherwise the process-wide
    REPRO_REAP_AGE default applies.  0 disables the reaper entirely —
    the branch is TRACE-TIME, so a disabled reaper compiles the exact
    step the pre-reaper engine compiled (no extra ops, bit-identical).

    Age is measured as ``t - itime`` (cycles since generation), which
    upper-bounds the time a packet has been PARKED on the -1
    non-channel (a packet cannot strand before it exists): no packet
    ever stays parked longer than `reap_age` cycles, though a packet
    that traveled before stranding is reaped correspondingly earlier.
    Using generation age avoids a per-slot park-time state array and
    keeps the reap decision a pure function of the request row.
    """
    age = int(getattr(cfg, "reap_age", 0))
    return age if age > 0 else env_int("REPRO_REAP_AGE", 0)


@jax.tree_util.register_dataclass
@dataclass
class SimStats:
    """Measurement accumulators (zeroed at the end of warmup).

    All fields are cumulative counters except `stranded`, a per-cycle
    GAUGE: the number of head-of-line requests currently parked on the
    -1 non-channel (packets a warm fault left with no route, see the
    updown kernel).  Its final value is the stranded population at exit
    — previously only inferable as "in flight when the run ended".

    `reaped` is the router-death reaper's cumulative drop counter
    (`resolve_reap_age`): parked packets whose age reached the park age
    are removed from their buffers and tallied here, DISJOINT from
    `dropped` (source-queue overflow), so exact conservation is
    ``generated == delivered + dropped + reaped + in-flight`` at every
    cycle — including across repair-epoch boundaries, where a table
    swap can unstrand a parked packet before the reaper reaches it.
    With the reaper on, `stranded` gauges the POST-reap parked
    population of the cycle.

    `occ_peak` is a high-water mark, not a per-measure counter: the
    maximum number of LIVE request rows (non-empty (channel, vc)
    buffers + non-empty source queues, taken right after inject) any
    cycle of the run saw.  It spans warmup too (`stats.zero_stats`
    preserves it across the reset): the occupancy-compacted step
    (`step_impl="compact"`, fused.py) uses it to certify post-run that
    its capacity rung C bounded the live set for the WHOLE run, and a
    warmup-phase overflow is just as invalidating as a measured one.
    Every step impl computes it from the same dense counts, so it is
    part of the bit-identity contract like any other counter.
    """

    delivered: jax.Array      # [] packets ejected
    lat_sum: jax.Array        # [] float32 sum of generation->ejection cycles
    generated: jax.Array      # [] packets generated (incl. dropped)
    dropped: jax.Array        # [] source-queue overflow
    stranded: jax.Array       # [] gauge: requests parked on the -1 channel
    reaped: jax.Array         # [] packets the reaper dropped (age-based)
    occ_peak: jax.Array       # [] high-water mark of live request rows
    hops: jax.Array           # [NUM_CH_TYPES] channel traversals by type

    def replace(self, **kw) -> "SimStats":
        return replace(self, **kw)

    @classmethod
    def zeros(cls, batch: tuple[int, ...] = ()) -> "SimStats":
        z = lambda *s: jnp.zeros(batch + s, dtype=jnp.int32)
        return cls(delivered=z(), lat_sum=jnp.zeros(batch, jnp.float32),
                   generated=z(), dropped=z(), stranded=z(), reaped=z(),
                   occ_peak=z(), hops=z(NUM_CH_TYPES))


@jax.tree_util.register_dataclass
@dataclass
class SimState:
    """All mutable router/terminal state, over (channel E, VC NV, slot S)
    and (terminal T, source-queue slot Q); ring buffers of packets."""

    # per-(channel, vc) input buffers; the trailing axis packs the packet
    # record (F_DEST destination terminal, F_ITIME generation cycle,
    # F_MIS misroute W-group (-1 = minimal), F_META routing meta bitfield,
    # F_READY cycle the head becomes forwardable)
    b_pkt: jax.Array          # [E, NV, S, NUM_FIELDS]
    b_head: jax.Array         # [E, NV] ring head
    b_count: jax.Array        # [E, NV] occupancy (packets)
    # per-terminal source queues (trailing axis: F_DEST, F_ITIME, F_MIS)
    s_pkt: jax.Array          # [T, Q, NUM_SRC_FIELDS]
    s_head: jax.Array         # [T]
    s_count: jax.Array        # [T]
    ch_busy: jax.Array        # [E] serialization busy countdown
    stats: SimStats

    def replace(self, **kw) -> "SimState":
        return replace(self, **kw)


@span("repro.build.lanes")
def make_state(net: Network, cfg, NV: int,
               batch: tuple[int, ...] = (), *,
               ch_pad: int = 0, term_pad: int = 0) -> SimState:
    """Fresh (empty-network) state; `batch` prepends sweep axes.

    `ch_pad` / `term_pad` append GHOST channels/terminals (used by the
    channel-sharded fused step so every shard's block is dense; see
    `fused.fused_pad`).  Ghosts start empty, are dead in every alive
    mask, and never inject — an all-zero state is already correct for
    them.

    The record width follows `cfg.step_impl`: the fused and compact
    steps carry the cached route fields (`NUM_FUSED_FIELDS`), the
    oracle the base payload (`NUM_FIELDS`)."""
    E, T = net.num_channels + ch_pad, net.num_terminals + term_pad
    S, Q = cfg.buf_pkts, cfg.srcq_pkts
    nf = (NUM_FUSED_FIELDS
          if getattr(cfg, "step_impl", "jnp") in CACHED_ROUTE_IMPLS
          else NUM_FIELDS)
    z = lambda *s: jnp.zeros(batch + s, dtype=jnp.int32)
    return SimState(
        b_pkt=z(E, NV, S, nf),
        b_head=z(E, NV), b_count=z(E, NV),
        s_pkt=z(T, Q, NUM_SRC_FIELDS),
        s_head=z(T), s_count=z(T),
        ch_busy=z(E),
        stats=SimStats.zeros(batch))


def build_consts(net: Network, cfg):
    """Static (per-net, per-cfg) arrays + the route KERNEL.

    Everything here is batch-free: phase functions gather from these with
    (possibly batched) indices, which keeps them pure under `vmap`.  The
    fault-dependent data (routing tables, alive masks) is deliberately NOT
    here — it lives in the per-lane `fl` dict (`build_lane`) threaded
    through the step arguments, so one compiled step serves lanes with
    different fault sets.
    """
    NV = num_vcs(net.meta["kind"], cfg.vc_mode, cfg.nonminimal) \
        * cfg.vcs_per_class
    E = net.num_channels
    T = net.num_terminals
    route_kernel = make_route_kernel(net, cfg.vc_mode)
    ser = (cfg.pkt_len + net.ch_bw - 1) // net.ch_bw  # serialization cycles
    wg_tbl = net.tables.get("node_wg", net.tables.get("node_grp"))
    # wg of the downstream node of each channel (for misroute clearing)
    ch_dst_wg = wg_tbl[np.clip(net.ch_dst, 0, net.num_nodes - 1)]
    consts = dict(
        NV=NV, E=E, T=T,
        # eject channels are the trailing id block (Network.validate); they
        # never request, so the request grid covers only [:E_req]
        E_req=net.first_eject,
        ch_dst=jnp.asarray(net.ch_dst),
        ch_ser=jnp.asarray(ser),
        # packed per-channel record (type, dst_wg, lat): the request phase
        # gathers it ONCE per requester instead of three separate row
        # gathers spread over arbitrate/stats/apply
        ch_tbl=jnp.stack([jnp.asarray(net.ch_type),
                          jnp.asarray(ch_dst_wg),
                          jnp.asarray(net.ch_lat)], axis=-1),
        inject_ch=jnp.asarray(net.inject_ch),
        term_node=jnp.asarray(net.term_node),
        term_wg=jnp.asarray(wg_tbl[net.term_node]),
        num_wg=net.meta["g"],
    )
    return consts, route_kernel


# additive UGAL congestion penalty per unit of W-group degradation: a
# candidate intermediate W-group that lost fraction d of its internal
# (mesh + local) channels reads round(SCALE * d) extra buffered packets on
# its sensor, biasing the adaptive misroute away from degraded W-groups.
# Zero on a pristine network, so fault-free UGAL decisions are unchanged.
UGAL_WG_PENALTY_SCALE = 16


@span("repro.build.lanes")
def build_lane(net: Network, cfg,
               faults: FaultSet | FaultSchedule | None = None) -> dict:
    """Per-lane fault data (the `fl` pytree): alive masks + fault-dependent
    routing tables (+ adaptive-misroute tables for the non-minimal modes,
    + UGAL sensors when adaptive routing is on).

    One lane describes ONE degraded (or pristine) network.  With a
    `FaultSchedule` the lane is EPOCH-STACKED: every array carries a
    leading `[P]` epoch axis plus an `epoch_start [P]` int32 vector, and
    the step resolves the active epoch by the traced cycle number
    (`resolve_epoch`) before the phases run — mid-run link death is just
    the epoch index advancing.

    The dict is a JAX pytree with a fixed structure per (net, cfg,
    schedule shape), so `stack_lanes` can prepend a lane axis and
    `run_scan_batched` can vmap the step over lanes carrying DIFFERENT
    fault sets (or schedules) in a single compile.  The `SimState` itself
    needs no fault information: buffers start empty and dead channels
    simply never grant.
    """
    if isinstance(faults, FaultSchedule):
        from ..routing import stack_epoch_dicts
        starts, fl = stack_epoch_dicts(
            [_build_epoch(net, cfg, f) for _, f in faults.epochs],
            (c for c, _ in faults.epochs))
        fl["epoch_start"] = starts
        return fl
    return _build_epoch(net, cfg, faults)


def _build_epoch(net: Network, cfg, faults: FaultSet | None) -> dict:
    """The flat (single-epoch) lane dict for one cold fault set."""
    from .inject import build_ugal_watch  # local import: step imports both
    faults = faults or FaultSet()
    fl = dict(
        ch_alive=jnp.asarray(faults.ch_alive(net)),
        term_alive=jnp.asarray(faults.term_alive(net)),
    )
    fl.update(route_tables(net, cfg.vc_mode, faults))
    if cfg.route_mode != "min":
        # fault-aware adaptive misroute stage: candidate intermediate
        # W-groups must keep alive global connectivity on both misroute
        # hops, and degraded W-groups are biased against in proportion to
        # their lost internal channels (see inject.make_misroute_fn)
        fl["glob_ok"] = jnp.asarray(glob_pair_alive(net, faults))
        frac = wg_channel_alive_frac(net, faults)
        fl["wg_penalty"] = jnp.asarray(
            np.round(UGAL_WG_PENALTY_SCALE * (1.0 - frac)).astype(np.int32))
    if cfg.route_mode == "ugal":
        fl["ugal_watch"] = build_ugal_watch(net, cfg, faults)
    return fl


def is_scheduled(fl: dict) -> bool:
    """True when the lane dict is epoch-stacked (carries `epoch_start`)."""
    return "epoch_start" in fl


def epoch_index(fl: dict, t):
    """Traced index of the epoch in effect at cycle `t` (int32 scalar)."""
    return (jnp.sum(t >= fl["epoch_start"]) - 1).astype(jnp.int32)


def lane_epoch(fl: dict, idx):
    """Slice one epoch out of an epoch-stacked lane dict; `idx` may be a
    traced scalar (the gather on the leading axis stays jit/vmap-legal)."""
    return {k: v[idx] for k, v in fl.items() if k != "epoch_start"}


def resolve_epoch(fl: dict, t):
    """The lane's fault data in effect at cycle `t`: a no-op for flat
    (cold) lanes, an epoch gather for scheduled ones.  The branch is
    trace-time (pytree structure is static under jit)."""
    if not is_scheduled(fl):
        return fl
    return lane_epoch(fl, epoch_index(fl, t))


def stack_lanes(lanes: list[dict], epochs: int | None = None) -> dict:
    """Stack per-lane fault dicts into one lane-axis pytree [B, ...].

    Epoch-stacked lanes with differing epoch counts are padded to the
    longest schedule by repeating their final epoch with an unreachable
    onset cycle, so heterogeneous warm-fault grids still stack into one
    dense `[B, P, ...]` pytree (and one compile).  `epochs` pins the
    padded epoch count to AT LEAST that many — window-session packers
    use it so every pack of a bucket stacks to the same [B, P, ...]
    shapes regardless of which lanes happened to land in it."""
    if lanes and is_scheduled(lanes[0]):
        P = max(int(l["epoch_start"].shape[0]) for l in lanes)
        if epochs is not None:
            P = max(P, epochs)
        lanes = [_pad_epochs(l, P) for l in lanes]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *lanes)


def _pad_epochs(fl: dict, P: int) -> dict:
    p = P - int(fl["epoch_start"].shape[0])
    if p == 0:
        return fl
    out = {k: jnp.concatenate([v] + [v[-1:]] * p) for k, v in fl.items()
           if k != "epoch_start"}
    out["epoch_start"] = jnp.concatenate(
        [fl["epoch_start"], jnp.full((p,), INF32, dtype=jnp.int32)])
    return out
