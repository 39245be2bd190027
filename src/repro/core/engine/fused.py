"""The fused cycle step (`SimConfig.step_impl="fused"`, the default).

`step_impl="jnp"` (`step.make_step`) is the classic phase pipeline and
stays the oracle, reached by name.  This module is the restructured hot
path, bit-identical to the oracle by construction (integer ops only, same
eligibility algebra, same tie-break order), built around two
observations:

ROUTE ONCE PER HOP, NOT ONCE PER CYCLE.  A packet's route out of a
channel — output channel, requested VC class, next routing meta — is a
pure function of (its record fields, the channel it sits in, the lane's
fault data), NOT of the cycle count.  The oracle re-evaluates it for
every one of the ``N = E_req*NV + T`` head rows every cycle; the fused
step evaluates it exactly once per hop, densely over the E winner rows
when a packet is PUSHED, and caches the three outputs in the packet
record (`state.F_OUT`/`F_CLS`/`F_META2`, the fused-only record tail) —
the request phase then reads routes out of the same gather that reads
the payload.  Epoch-scheduled (warm-fault) lanes fall back to per-cycle
routing: a cached decision could straddle an epoch boundary.  The
fallback is a trace-time branch on the lane pytree structure
(`state.is_scheduled`); cold-fault and pristine lanes — every paper
figure sweep — take the cached path.

ONE WINNER PER CHANNEL DRIVES EVERYTHING.  Age-based grant yields at
most one winner per output channel, so grant and apply are driven from
a dense per-channel winner table instead of per-request-row scatters:

  * grant is ONE `segment_min` into E (+1 junk) segments of the packed
    ``itime * R2 + row`` key (lexicographic min IS oldest-age,
    smallest-row-id; the step falls back to the oracle's two-pass
    age-then-priority form when the packed key would overflow int32).
    Credit/eject eligibility is ONE vectorized per-row gather of the
    dense per-(channel, class) credit table; busy/alive are dense
    per-channel masks applied after the reduction.
  * winners' records come from two E-row gathers (buffer heads / source
    queues) selected by the winner row id; pops are recovered per row
    by comparing each row's output channel's winner id against its own
    row id (a vectorized gather + compare — scatter-free); the push is
    the single E-row scatter left in the cycle.

The winner's physical VC and target occupancy (the oracle's `expand_vcs`
outputs) are reconstructed channel-dense from the per-class occupancy
min/argmin tables — the winning row requested exactly the
least-occupied VC of its class, so the dense lookup is the same value.
Stats are accumulated channel-dense from the winner table; the sums are
exact int32, so they equal the oracle's row sums bit for bit.

Channel sharding (the 2-D ``(lanes, shards)`` mesh, `engine.sweep`): with
``shards=K`` and a shard axis name, each device owns one contiguous block
of the channel-id space — the eject-channel block trails the id space, so
the partition is a plain slice.  The BIG state arrays (`b_pkt`, `s_pkt`)
are block-partitioned on their channel/terminal axis; the small
credit/serialization state (`b_count`, `b_head`, `ch_busy`, `s_head`,
`s_count`) stays replicated and is advanced identically on every shard
from the exchanged winner table.  The halo exchange at the phase boundary
is exactly two collectives + one scalar:

  * `lax.pmin` of the dense ``[E']`` per-channel grant minima (each
    shard reduces its own request rows; a channel's eligible rows may
    live on any shard — its buffer rows on the channel-owner shard, its
    injection row on the terminal-owner shard),
  * `lax.psum` of the dense ``[E', 5]`` winner-record table (exactly one
    shard owns each winning row; everyone else contributes zeros), and
  * `lax.psum` of the scalar stranded-request gauge.

Row priorities use GLOBAL channel/terminal ids (buffer row (c, v) has
priority ``c*NV + v``, source row t has ``E'*NV + t``), so the sharded
run's winners — and therefore every counter — are bit-identical to the
single-device run, lane for lane and cycle for cycle (pinned by
tests/test_channel_sharding.py; the priority VALUES differ from the
unsharded row ids, but the relative order of eligible rows is the same:
buffer rows sort by (channel, vc) and precede source rows in both
schemes, so every age tie resolves to the same packet).  Non-dividing
channel/terminal counts are padded with ghost entries (dead, never
eligible, zero stats).

`cfg.grant_impl="pallas"` routes the grant reduction of the UNSHARDED
fused and compact steps through the `repro.kernels.netsim` `cycle_core`
Pallas kernel (interpret mode on CPU, compiled on TPU).  The kernel
needs the packed key, and the sharded variant's global minimum only
exists after the `pmin` exchange, so a requested kernel raises under
the two-pass form or channel sharding instead of being replaced by the
jnp segment-min.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ... import env_int
from ..spans import phase
from ..topology import EJECT, NUM_CH_TYPES, Network
from ..traffic import as_pattern
from .inject import make_inject_fn, make_misroute_fn
from .state import (F_CLS, F_DEST, F_ITIME, F_META, F_META2, F_MIS,
                    F_OUT, F_READY, INF32, build_consts, is_scheduled,
                    resolve_epoch, resolve_reap_age)
from .stats import live_rows

# winner-record columns (the dense [E, 5] table exchanged across shards):
# destination, generation cycle, misroute wg, meta-to-store, class
W_DEST, W_ITIME, W_MIS, W_META, W_CLS = range(5)
NUM_W_FIELDS = 5


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad1(x, pad, fill=0):
    x = np.asarray(x)
    if pad == 0:
        return jnp.asarray(x)
    return jnp.asarray(np.concatenate(
        [x, np.full((pad,) + x.shape[1:], fill, x.dtype)]))


def grant_form(net: Network, cfg, shards: int = 1) -> str:
    """Which grant form the fused step compiles for this (net, cfg):
    ``"combined"`` — one packed ``itime * R2 + prio`` segment-min — or
    ``"two_pass"`` — the oracle's age-then-priority fallback, taken when
    the packed key could exceed int32 (``cycles * R2 + R2 - 1``).

    Single source of truth for the overflow predicate: the step builders
    below, `SweepResult.grant_form` reporting, and the static spec pass
    (`repro.analysis`) all call this instead of re-deriving the interval
    bound.  ``shards`` matters because the K-way channel shard packs
    GLOBAL row priorities over the ghost-padded ``Ep * NV + Tp`` id
    space, a strictly larger modulus than the unsharded request grid's
    ``E_req * NV + T``.
    """
    from ..routing import num_vcs
    NV = (num_vcs(net.meta["kind"], cfg.vc_mode, cfg.nonminimal)
          * cfg.vcs_per_class)
    if shards <= 1:
        N = net.first_eject * NV + net.num_terminals
    else:
        ch_pad, term_pad = fused_pad(net, shards)
        N = ((net.num_channels + ch_pad) * NV
             + net.num_terminals + term_pad)
    R2 = _pow2(N)
    cycles = cfg.warmup + cfg.measure
    return ("combined" if cycles * R2 + (R2 - 1) < 2**31 - 1
            else "two_pass")


def fused_pad(net: Network, shards: int) -> tuple[int, int]:
    """(ch_pad, term_pad) ghost padding a K-way channel shard needs so
    each shard's block is dense (`make_state(..., ch_pad, term_pad)` pads
    the state arrays; the step pads its own static tables)."""
    E, T = net.num_channels, net.num_terminals
    return _round_up(E, shards) - E, _round_up(T, shards) - T


def make_fused_step(net: Network, cfg, pattern, inject_mask=None, *,
                    shards: int = 1, shard_axis: str = "shards"):
    """Returns (step, consts); signature-compatible with `step.make_step`.

    ``shards=1`` is the single-device fused step (a drop-in for the
    oracle step).  ``shards=K > 1`` builds the channel-sharded variant
    meant to run INSIDE a `shard_map` over a mesh axis named
    `shard_axis`; its state must be padded to the sharded sizes
    (`make_state(..., ch_pad=..., term_pad=...)` with `fused_pad`)."""
    pattern, inject_mask = as_pattern(pattern, inject_mask)
    consts, route_kernel = build_consts(net, cfg)
    if shards <= 1:
        step = _make_unsharded(net, cfg, pattern, inject_mask, consts,
                               route_kernel)
    else:
        step = _make_sharded(net, cfg, pattern, inject_mask, consts,
                             route_kernel, shards, shard_axis)
    return step, consts


def _occ_tables(b_count, NC, vpc):
    """Per-(channel, class) least-occupied-VC tables: (occ_min [E, NC],
    occ_arg [E, NC]).  Dense elementwise; `jnp.argmin` picks the first
    minimum exactly like the oracle's `expand_vcs` row gather."""
    E = b_count.shape[0]
    occ = b_count.reshape(E, NC, vpc)
    return occ.min(-1), jnp.argmin(occ, -1).astype(jnp.int32)


def _winner_vc(wcls, occ_min, occ_arg, NC, vpc):
    """(wvc [E], wovc [E]) for the winner table: the winning row asked
    for the least-occupied VC of its class, so a dense one-hot select
    over the NC class columns reproduces `expand_vcs`' per-row values."""
    csel = wcls[:, None] == jnp.arange(NC, dtype=jnp.int32)[None, :]
    wovc = jnp.where(csel, occ_min, 0).sum(1)
    wvc = wcls * vpc + jnp.where(csel, occ_arg, 0).sum(1)
    return wvc, wovc


def _row_elig(elig_ck, out, cls, E):
    """Vectorized per-row credit/eject eligibility: one gather of the
    dense [E, NC] table at each row's (output channel, class)."""
    return elig_ck[(jnp.clip(out, 0, E - 1), cls)]


def _grant(ok, out, itime, prio, ch_ok, E, R2, use_combined):
    """Per-channel age-based grant over the request rows: one (or, in
    the two-pass int32-overflow fallback, two) segment_min into E (+1
    junk) segments, then the dense busy/alive channel mask.  Returns
    (won_ch [E], wprio [E]): the winner's row priority per granting
    channel."""
    seg = jnp.where(ok, out, E)
    if use_combined:
        key = jnp.where(ok, itime * R2 + prio, INF32)
        m = jax.ops.segment_min(key, seg, num_segments=E + 1)[:E]
        m = jnp.where(ch_ok, m, INF32)
        won_ch = m != INF32
        return won_ch, jnp.where(won_ch, m & (R2 - 1), 0)
    m1 = jax.ops.segment_min(jnp.where(ok, itime, INF32), seg,
                             num_segments=E + 1)
    tie = ok & (itime == m1[jnp.where(ok, out, 0)])
    m2 = jax.ops.segment_min(jnp.where(tie, prio, INF32), seg,
                             num_segments=E + 1)[:E]
    won_ch = ch_ok & (m1[:E] != INF32)
    return won_ch, jnp.where(won_ch, m2, 0)


def _use_pallas(cfg, use_combined: bool) -> bool:
    """Whether the step grants through the `cycle_core` kernel.  The
    kernel needs the packed key, so a requested ``grant_impl="pallas"``
    under the two-pass form is an error, never a silent `segment_min`."""
    if getattr(cfg, "grant_impl", "jnp") != "pallas":
        return False
    if not use_combined:
        raise ValueError(
            "grant_impl='pallas' needs the packed grant key, but "
            f"{cfg.warmup + cfg.measure} cycles overflow it (grant_form "
            "'two_pass'); shorten the run or use grant_impl='jnp'")
    return True


def compact_rows(net: Network, cfg) -> int:
    """N, the unsharded request-row count (`E_req * NV + T`) — the
    compact step's capacity ladder is sized against this."""
    from ..routing import num_vcs
    NV = (num_vcs(net.meta["kind"], cfg.vc_mode, cfg.nonminimal)
          * cfg.vcs_per_class)
    return net.first_eject * NV + net.num_terminals


def capacity_ladder(N: int) -> tuple[int, ...]:
    """The compact step's capacity rungs for an N-row request grid:
    ``ceil(N/8) < ceil(N/4) < ceil(N/2) < N`` (deduplicated for tiny N).
    Each rung is a distinct compiled executable; the top rung C = N can
    never overflow, so the escalation walk always terminates."""
    return tuple(sorted({-(-N // 8), -(-N // 4), -(-N // 2), N}))


def next_rung(N: int, floor: int) -> int:
    """The smallest ladder rung >= `floor` (the escalation target when a
    run's `occ_peak` reached `floor`); N when `floor` exceeds the top."""
    for r in capacity_ladder(N):
        if r >= floor:
            return r
    return N


def initial_capacity(N: int) -> int:
    """The rung a compact step starts at: the smallest ladder rung that
    covers REPRO_COMPACT_CAP when set (so ``REPRO_COMPACT_CAP=1`` pins
    the bottom rung and a large value pins C = N), else ``ceil(N/4)`` —
    paper-figure sweeps peak well under N/4 live rows even at
    saturation, with headroom to spare (see docs/performance.md)."""
    cap = env_int("REPRO_COMPACT_CAP", 0)
    if cap > 0:
        return next_rung(N, min(cap, N))
    ladder = capacity_ladder(N)
    return ladder[1] if len(ladder) > 1 else ladder[0]


def make_compact_step(net: Network, cfg, pattern, inject_mask=None, *,
                      capacity: int | None = None):
    """The occupancy-compacted fused step (`cfg.step_impl="compact"`):
    returns (step, consts), signature-compatible with `step.make_step`.

    Identical cycle semantics to the unsharded fused step, but the
    request phase first COMPACTS the live rows (non-empty (channel, vc)
    buffers + non-empty source queues) into a statically-bounded active
    set of `capacity` C rows, so the head gather, the route fallback,
    the packed segment-min grant key, and the pop decode all run over C
    rows instead of all ``N = E_req*NV + T`` — per-cycle cost tracks
    OCCUPANCY, not network capacity.  The compaction is a stable
    partition (cumsum of the live mask + one binary-search gather), so
    active slot k holds the k-th live row in the oracle's row order and
    each slot's grant priority is its GLOBAL row id — the packed
    ``itime * R2 + prio`` keys, and therefore every winner and every
    counter, are bit-identical to the oracle's whenever C bounds the
    live set.

    C not bounding the live set is DETECTED, never silent: the step
    folds the exact live-row census (computed densely, independent of
    C) into `SimStats.occ_peak` every cycle, and the sweep layer
    re-dispatches the whole grid at the next ladder rung when a run's
    peak crossed its rung (`sweep._PendingLanes.finish`) — the rerun is
    deterministic, so escalated results are still bit-identical to the
    oracle.  `capacity=None` starts at `initial_capacity(N)`
    (REPRO_COMPACT_CAP pins the starting rung).

    Not channel-shardable (the active set is a global permutation);
    warm-fault (epoch-scheduled) lanes fall back to per-cycle routing
    over the C active rows, exactly like the fused step does over N.
    """
    pattern, inject_mask = as_pattern(pattern, inject_mask)
    consts, route_kernel = build_consts(net, cfg)
    N = consts["E_req"] * consts["NV"] + consts["T"]
    C = initial_capacity(N) if capacity is None else int(capacity)
    if not 1 <= C <= N:
        raise ValueError(f"compact capacity {C} outside [1, {N}]")
    step = _make_compact(net, cfg, pattern, inject_mask, consts,
                         route_kernel, C)
    # reporting hooks for the sweep layer (rung bookkeeping without
    # re-deriving the row count)
    step.compact_capacity = C
    step.compact_rows = N
    return step, consts


def _make_compact(net, cfg, pattern, inject_mask, consts, route_kernel,
                  C):
    inject = make_inject_fn(net, cfg, consts, pattern, inject_mask)
    NV, E, T, ER = consts["NV"], consts["E"], consts["T"], consts["E_req"]
    S, Q = cfg.buf_pkts, cfg.srcq_pkts
    vpc = cfg.vcs_per_class
    NC = NV // vpc
    N = ER * NV + T
    R2 = _pow2(N)
    use_combined = grant_form(net, cfg) == "combined"
    use_pallas = _use_pallas(cfg, use_combined)
    if use_pallas:
        from ...kernels.netsim.ops import cycle_core
    reap_age = resolve_reap_age(cfg)   # 0 = reaper off (trace-time)

    ch_dst = consts["ch_dst"]
    ch_tbl = consts["ch_tbl"]
    ch_type, ch_dst_wg, ch_lat = (ch_tbl[:, 0], ch_tbl[:, 1],
                                  ch_tbl[:, 2])
    ch_ser = consts["ch_ser"]
    is_ej_ch = ch_type == EJECT
    inject_ch = consts["inject_ch"]
    slot_iota = jnp.arange(C, dtype=jnp.int32)
    ch_iota = jnp.arange(E, dtype=jnp.int32)
    row_iota = jnp.arange(N, dtype=jnp.int32)
    vc_iota = jnp.arange(NV, dtype=jnp.int32)
    type_iota = jnp.arange(NUM_CH_TYPES, dtype=jnp.int32)

    def step(state, t_key_rate_fl):
        t, key, rate_pkt, fl = t_key_rate_fl
        cached = not is_scheduled(fl)   # trace-time, as in the fused step
        fl = resolve_epoch(fl, t)
        with phase("inject"):
            state = inject(state, t, key, rate_pkt, fl)

        # live-row census + stable compaction.  `occ` is EXACT (dense,
        # independent of C) — it feeds the occ_peak certificate the
        # escalation check relies on.  The live-mask prefix sum is built
        # two-level so the serial scans stay short (an NV-wide axis
        # cumsum vectorized over the ER channels, then channel- and
        # terminal-level cumsums); live row r lands in active slot
        # cs[r]-1 by a stable scatter (the dispatch planner runs compact
        # lanes sequentially, where the unbatched scatter beats the
        # binary-search gather form — vmapped lanes would invert that,
        # but they take the mesh path).  Slots past the live count keep
        # the N sentinel, so `aid` stays sorted (stable compaction
        # preserves row order) for the winner-slot search below.
        with phase("compact"):
            lb = (state.b_count[:ER] > 0).astype(jnp.int32)     # [ER, NV]
            within = jnp.cumsum(lb, axis=-1)
            ch_tot = within[:, -1]
            base = jnp.cumsum(ch_tot)                           # [ER]
            scs = jnp.cumsum((state.s_count > 0).astype(jnp.int32))
            occ = base[-1] + scs[-1]
            cs = jnp.concatenate(
                [((base - ch_tot)[:, None] + within).reshape(-1),
                 base[-1] + scs])                               # [N]
            live = jnp.concatenate(
                [lb.reshape(-1) > 0, state.s_count > 0])        # [N]
            aid = jnp.full((C,), N, jnp.int32).at[
                jnp.where(live, cs - 1, C)].set(row_iota, mode="drop")  # [C]
            slot_ok = slot_iota < jnp.minimum(occ, C)

        # per-slot request assembly: ONE C-row head gather (the fused
        # step's ER*NV-row gather, shrunk to the live set) + one C-row
        # source-queue gather, merged by slot kind
        with phase("route"):
            is_buf = aid < ER * NV
            e = jnp.clip(aid // NV, 0, ER - 1)
            v = jnp.clip(aid, 0, ER * NV - 1) % NV
            tt = jnp.clip(aid - ER * NV, 0, T - 1)
            bh = state.b_head[(e, v)]                            # [C]
            brec = state.b_pkt[(e, v, bh)]                       # [C, 8]
            srec = state.s_pkt[(tt, state.s_head[tt])]           # [C, 3]
            ready = ~is_buf | (brec[:, F_READY] <= t)
            valid = slot_ok & ready
            if cached:
                out_b, cls_b, meta2_b = (brec[:, F_OUT], brec[:, F_CLS],
                                         brec[:, F_META2])
            else:
                out_b, cls_b, meta2_b = route_kernel(
                    fl, ch_dst[e], brec[:, F_DEST], brec[:, F_MIS],
                    brec[:, F_META])
            out = jnp.where(is_buf, out_b, inject_ch[tt]).astype(jnp.int32)
            cls = jnp.where(is_buf, cls_b, 0).astype(jnp.int32)
            itime = jnp.where(is_buf, brec[:, F_ITIME], srec[:, F_ITIME])
            dest = jnp.where(is_buf, brec[:, F_DEST], srec[:, F_DEST])
            mis = jnp.where(is_buf, brec[:, F_MIS], srec[:, F_MIS])
            meta2 = jnp.where(is_buf, meta2_b, 0).astype(jnp.int32)
            rowok = valid & (out >= 0)
        # router-death reaper over the active rows: undeliverable rows
        # (parked on -1 OR requesting a dead channel — see
        # stats.undeliverable_mask) are live, so whenever occ <= C they
        # are ALL in the active set — the reap mask is exact under the
        # same occ_peak certificate that covers the grant
        with phase("stats"):
            if reap_age:
                undel = valid & ((out < 0)
                                 | ~fl["ch_alive"][jnp.clip(out, 0, E - 1)])
                reap = undel & (t - itime >= reap_age)
            else:
                undel = reap = None
        prio = aid      # the global row id IS the oracle's tie-break

        # grant over the C active rows — same segments, same packed
        # keys, same winners as the fused step's N-row reduction
        with phase("route"):
            occ_min, occ_arg = _occ_tables(state.b_count, NC, vpc)
        with phase("grant"):
            elig_ck = (occ_min < S) | is_ej_ch[:, None]
            ok = rowok & _row_elig(elig_ck, out, cls, E)
            ch_ok = (state.ch_busy == 0) & fl["ch_alive"]
            if use_pallas:
                won_ch, wprio, win_slot = cycle_core(out, itime, ok, ch_ok,
                                                     r2=R2, prio=prio)
            else:
                won_ch, wprio = _grant(ok, out, itime, prio, ch_ok, E, R2,
                                       use_combined)
                win_slot = None

        # dense winner table: map each granting channel's winning row
        # id back to its active slot (aid is sorted, so one binary
        # search), then ONE [E, 5]-gather of the compacted records
        with phase("apply"):
            wslot_i = jnp.clip(
                jnp.searchsorted(aid, wprio, side="left"), 0, C - 1)
            crec = jnp.stack([dest, itime, mis, meta2, cls], axis=-1)
            w = crec[wslot_i]                                     # [E, 5]
            wdest, witime = w[:, W_DEST], w[:, W_ITIME]
            wmis, wmeta, wcls = w[:, W_MIS], w[:, W_META], w[:, W_CLS]
            wvc, wovc = _winner_vc(wcls, occ_min, occ_arg, NC, vpc)
            entered = (wmis >= 0) & (ch_dst_wg == wmis)
            wmis = jnp.where(entered, -1, wmis)
            push = won_ch & ~is_ej_ch
            whead = state.b_head[(ch_iota, jnp.clip(wvc, 0, NV - 1))]
            wslot = (whead + wovc) % S
        if cached:
            with phase("route"):
                out2, cls2, meta2_n = route_kernel(fl, ch_dst, wdest, wmis,
                                                   wmeta)
                tail = [out2.astype(jnp.int32), cls2.astype(jnp.int32),
                        meta2_n.astype(jnp.int32)]
        else:
            z = jnp.zeros_like(wdest)
            tail = [z, z, z]
        with phase("apply"):
            new_rec = jnp.stack(
                [wdest, witime, wmis, wmeta, t + ch_lat] + tail, axis=-1)
            pe = jnp.where(push, ch_iota, E)
            b_pkt = state.b_pkt.at[(pe, wvc, wslot)].set(new_rec,
                                                         mode="drop")

            # pops: the fused step's N-row gather+compare shrinks to C; the
            # per-(channel, vc) / per-terminal pop bookkeeping stays in the
            # dense one-hot form — XLA:CPU vectorizes the [E, NV] rebuilds
            # well, while the equivalent scatter chains lower to slow
            # row-at-a-time loops (measured ~2x worse)
            if win_slot is None:
                wprio_eff = jnp.where(won_ch, wprio, -1)
                won_slot = rowok & (wprio_eff[jnp.clip(out, 0, E - 1)]
                                    == aid)
            else:
                won_slot = win_slot
            # reaped rows pop like winners but push nowhere (masks disjoint:
            # a winner's out channel is live, a reap victim's is -1 or
            # dead); source rows are reapable too — a source head whose
            # injection channel died can never be granted
            pop_slot = won_slot if reap is None else won_slot | reap
            pe_b = jnp.where(pop_slot & is_buf, e, E)
            pop1 = jnp.zeros((E, NV), jnp.int32).at[(pe_b, v)].add(
                1, mode="drop")
            b_head = (state.b_head + pop1) % S
            vc_oh = wvc[:, None] == vc_iota[None, :]
            b_count = (state.b_count - pop1
                       + (push[:, None] & vc_oh).astype(jnp.int32))
            ts_m = jnp.where(pop_slot & ~is_buf, tt, T)
            pop_s = jnp.zeros((T,), jnp.int32).at[ts_m].add(1, mode="drop")
            s_head = (state.s_head + pop_s) % Q
            s_count = state.s_count - pop_s
            ch_busy = jnp.where(won_ch, ch_ser - 1,
                                jnp.maximum(state.ch_busy - 1, 0))

        # stats, channel-dense like the fused step; `stranded` counts
        # over the active rows (stranded rows are live, so they are all
        # in the active set whenever occ <= C)
        with phase("stats"):
            st = state.stats
            w_ej = won_ch & is_ej_ch
            hops = (won_ch[:, None]
                    & (ch_type[:, None] == type_iota[None, :]))
            if reap is None:
                stranded = (valid & (out < 0)).sum().astype(jnp.int32)
                reaped = st.reaped
            else:
                stranded = (undel & ~reap).sum().astype(jnp.int32)
                reaped = st.reaped + reap.sum().astype(jnp.int32)
            st = st.replace(
                delivered=st.delivered + w_ej.sum(),
                lat_sum=st.lat_sum + jnp.where(w_ej, t - witime, 0).sum(),
                hops=st.hops + hops.astype(jnp.int32).sum(0),
                stranded=stranded, reaped=reaped,
                occ_peak=jnp.maximum(st.occ_peak, occ))
        return state.replace(
            b_pkt=b_pkt, b_head=b_head, b_count=b_count,
            s_head=s_head, s_count=s_count, ch_busy=ch_busy,
            stats=st), None

    return step


def _make_unsharded(net, cfg, pattern, inject_mask, consts, route_kernel):
    inject = make_inject_fn(net, cfg, consts, pattern, inject_mask)
    NV, E, T, ER = consts["NV"], consts["E"], consts["T"], consts["E_req"]
    S, Q = cfg.buf_pkts, cfg.srcq_pkts
    vpc = cfg.vcs_per_class
    NC = NV // vpc
    N = ER * NV + T
    R2 = _pow2(N)
    # the combined int32 key needs headroom for the largest (itime, prio)
    # pair; fall back to the oracle's two-pass form when it would overflow
    # (`grant_form` is the shared predicate; the chosen form is surfaced
    # in `SweepResult.grant_form` and checked statically by the spec pass)
    use_combined = grant_form(net, cfg) == "combined"
    use_pallas = _use_pallas(cfg, use_combined)
    if use_pallas:
        from ...kernels.netsim.ops import cycle_core
    reap_age = resolve_reap_age(cfg)   # 0 = reaper off (trace-time)

    ch_dst = consts["ch_dst"]
    ch_tbl = consts["ch_tbl"]
    ch_type, ch_dst_wg, ch_lat = (ch_tbl[:, 0], ch_tbl[:, 1],
                                  ch_tbl[:, 2])
    ch_ser = consts["ch_ser"]
    is_ej_ch = ch_type == EJECT
    inject_ch = consts["inject_ch"]
    e_idx = jnp.arange(ER)[:, None].repeat(NV, 1)
    v_idx = jnp.arange(NV)[None, :].repeat(ER, 0)
    cur_rows = ch_dst[e_idx.reshape(-1)]
    zeros_t = jnp.zeros(T, jnp.int32)
    prio = jnp.arange(N, dtype=jnp.int32)
    row_id = prio
    ch_iota = jnp.arange(E, dtype=jnp.int32)
    vc_iota = jnp.arange(NV, dtype=jnp.int32)
    type_iota = jnp.arange(NUM_CH_TYPES, dtype=jnp.int32)

    def step(state, t_key_rate_fl):
        t, key, rate_pkt, fl = t_key_rate_fl
        cached = not is_scheduled(fl)   # trace-time: see module docstring
        fl = resolve_epoch(fl, t)
        with phase("inject"):
            state = inject(state, t, key, rate_pkt, fl)
        with phase("stats"):
            occ = live_rows(state)

        # request rows, in the oracle's order ([:ER]*NV buffer heads,
        # then T source queues) — `prio` IS the oracle's tie-break row id
        with phase("route"):
            bh = state.b_head[:ER]
            head = state.b_pkt[(e_idx, v_idx, bh)].reshape(ER * NV, -1)
            r_valid = ((state.b_count[:ER] > 0).reshape(-1)
                       & (head[:, F_READY] <= t))
            if cached:
                out_b, cls_b, meta2_b = (head[:, F_OUT], head[:, F_CLS],
                                         head[:, F_META2])
            else:
                out_b, cls_b, meta2_b = route_kernel(
                    fl, cur_rows, head[:, F_DEST], head[:, F_MIS],
                    head[:, F_META])
            sq = state.s_pkt[(jnp.arange(T), state.s_head)]
            out = jnp.concatenate([out_b, inject_ch]).astype(jnp.int32)
            cls = jnp.concatenate([cls_b, zeros_t]).astype(jnp.int32)
            itime = jnp.concatenate([head[:, F_ITIME], sq[:, F_ITIME]])
            valid = jnp.concatenate([r_valid, state.s_count > 0])
            rowok = valid & (out >= 0)
        # router-death reaper: undeliverable rows (parked on -1 OR
        # requesting a dead channel) past the park age — disjoint from
        # winners, which need a live channel (see stats.reap_mask)
        with phase("stats"):
            if reap_age:
                undel = valid & ((out < 0)
                                 | ~fl["ch_alive"][jnp.clip(out, 0, E - 1)])
                reap = undel & (t - itime >= reap_age)
            else:
                undel = reap = None

        # grant: per-row credit gather, one segment-min, dense channel
        # mask; at most one winner (row priority) per output channel
        with phase("route"):
            occ_min, occ_arg = _occ_tables(state.b_count, NC, vpc)
        with phase("grant"):
            elig_ck = (occ_min < S) | is_ej_ch[:, None]
            ok = rowok & _row_elig(elig_ck, out, cls, E)
            ch_ok = (state.ch_busy == 0) & fl["ch_alive"]
            if use_pallas:
                won_ch, wprio, win_row = cycle_core(out, itime, ok, ch_ok,
                                                    r2=R2)
            else:
                won_ch, wprio = _grant(ok, out, itime, prio, ch_ok, E, R2,
                                       use_combined)
                win_row = None

        # dense winner table: two E-row gathers (buffer / source rows)
        with phase("apply"):
            is_buf = wprio < ER * NV
            bclip = jnp.clip(wprio, 0, ER * NV - 1)
            wb = head[bclip]
            ws = sq[jnp.clip(wprio - ER * NV, 0, T - 1)]
            wdest = jnp.where(is_buf, wb[:, F_DEST], ws[:, F_DEST])
            witime = jnp.where(is_buf, wb[:, F_ITIME], ws[:, F_ITIME])
            wmis = jnp.where(is_buf, wb[:, F_MIS], ws[:, F_MIS])
            wmeta = jnp.where(
                is_buf,
                wb[:, F_META2] if cached else meta2_b[bclip],
                0).astype(jnp.int32)
            wcls = jnp.where(
                is_buf,
                wb[:, F_CLS] if cached else cls_b[bclip],
                0).astype(jnp.int32)
            wvc, wovc = _winner_vc(wcls, occ_min, occ_arg, NC, vpc)
            entered = (wmis >= 0) & (ch_dst_wg == wmis)
            wmis = jnp.where(entered, -1, wmis)
            push = won_ch & ~is_ej_ch
            vc_oh = wvc[:, None] == vc_iota[None, :]
            whead = jnp.where(vc_oh, state.b_head, 0).sum(1)
            wslot = (whead + wovc) % S
        if cached:
            # the route-once-per-hop evaluation: the pushed packet's
            # next-hop decision, dense over the E winner rows, with the
            # same (cleared-mis, meta-to-store) inputs the oracle feeds
            # its head-time call
            with phase("route"):
                out2, cls2, meta2 = route_kernel(fl, ch_dst, wdest, wmis,
                                                 wmeta)
                tail = [out2.astype(jnp.int32), cls2.astype(jnp.int32),
                        meta2.astype(jnp.int32)]
        else:
            z = jnp.zeros_like(wdest)
            tail = [z, z, z]
        with phase("apply"):
            new_rec = jnp.stack(
                [wdest, witime, wmis, wmeta, t + ch_lat] + tail, axis=-1)
            pe = jnp.where(push, ch_iota, E)
            b_pkt = state.b_pkt.at[(pe, wvc, wslot)].set(new_rec,
                                                         mode="drop")

            # pops, recovered per row by comparing each row's output
            # channel's winner id against its own row id — a vectorized
            # gather + compare, no scatter (the Pallas core already emits
            # this mask from the same comparison inside the kernel)
            if win_row is None:
                wprio_eff = jnp.where(won_ch, wprio, -1)
                won_row = rowok & (wprio_eff[jnp.clip(out, 0, E - 1)]
                                   == row_id)
            else:
                won_row = win_row
            # reaped rows pop like winners but push nowhere (disjoint
            # masks: a winner's out channel is live, a reap victim's is
            # -1 or dead); the source tail is reapable too, so pop_s
            # widens
            pop_row = won_row if reap is None else won_row | reap
            pop1 = jnp.pad(
                pop_row[: ER * NV].reshape(ER, NV).astype(jnp.int32),
                ((0, E - ER), (0, 0)))
            b_head = (state.b_head + pop1) % S
            b_count = (state.b_count - pop1
                       + (push[:, None] & vc_oh).astype(jnp.int32))
            pop_s = pop_row[ER * NV:].astype(jnp.int32)
            s_head = (state.s_head + pop_s) % Q
            s_count = state.s_count - pop_s
            ch_busy = jnp.where(won_ch, ch_ser - 1,
                                jnp.maximum(state.ch_busy - 1, 0))

        # stats, channel-dense (bit-equal to the oracle's row sums: the
        # winners biject the granting channels and the sums are int32)
        with phase("stats"):
            st = state.stats
            w_ej = won_ch & is_ej_ch
            hops = (won_ch[:, None]
                    & (ch_type[:, None] == type_iota[None, :]))
            if reap is None:
                stranded = (valid & (out < 0)).sum().astype(jnp.int32)
                reaped = st.reaped
            else:
                stranded = (undel & ~reap).sum().astype(jnp.int32)
                reaped = st.reaped + reap.sum().astype(jnp.int32)
            st = st.replace(
                delivered=st.delivered + w_ej.sum(),
                lat_sum=st.lat_sum + jnp.where(w_ej, t - witime, 0).sum(),
                hops=st.hops + hops.astype(jnp.int32).sum(0),
                stranded=stranded, reaped=reaped,
                occ_peak=jnp.maximum(st.occ_peak, occ))
        return state.replace(
            b_pkt=b_pkt, b_head=b_head, b_count=b_count,
            s_head=s_head, s_count=s_count, ch_busy=ch_busy,
            stats=st), None

    return step


def _make_sharded(net, cfg, pattern, inject_mask, consts, route_kernel,
                  K, axis):
    """The channel-sharded step: runs inside `shard_map`, owns the
    ``[Ek, NV, S, 8]`` / ``[Tk, Q, 3]`` blocks of `b_pkt` / `s_pkt` for
    its shard index, keeps the rest of the state replicated, and
    exchanges the per-channel grant minima (`pmin`) + winner records
    (`psum`) at the phase boundary."""
    if getattr(cfg, "grant_impl", "jnp") == "pallas":
        raise ValueError(
            "grant_impl='pallas' has no channel-sharded form; unset "
            "REPRO_CHANNEL_SHARDS or use grant_impl='jnp'")
    NV, E, T = consts["NV"], consts["E"], consts["T"]
    S, Q = cfg.buf_pkts, cfg.srcq_pkts
    vpc = cfg.vcs_per_class
    NC = NV // vpc
    ch_pad, term_pad = fused_pad(net, K)
    Ep, Tp = E + ch_pad, T + term_pad
    Ek, Tk = Ep // K, Tp // K
    R2 = _pow2(Ep * NV + Tp)                 # global-priority modulus
    use_combined = grant_form(net, cfg, K) == "combined"
    reap_age = resolve_reap_age(cfg)         # 0 = reaper off (trace-time)

    # padded static tables (ghost channels: dead, type -1; ghost
    # terminals: no injection channel, never generate)
    nn = net.num_nodes
    ch_dst = _pad1(np.clip(net.ch_dst, 0, nn - 1), ch_pad)
    tbl = np.asarray(consts["ch_tbl"])
    ch_type = _pad1(tbl[:, 0], ch_pad, -1)
    ch_dst_wg = _pad1(tbl[:, 1], ch_pad)
    ch_lat = _pad1(tbl[:, 2], ch_pad)
    ser = np.broadcast_to(np.asarray(consts["ch_ser"]), (E,))
    ch_ser = _pad1(ser, ch_pad, 1)
    inject_ch = _pad1(np.asarray(consts["inject_ch"]), term_pad, -1)
    is_ej_ch = ch_type == EJECT
    gen_mis = make_misroute_fn(net, cfg, consts)
    inj_mask = (jnp.ones(T, dtype=bool) if inject_mask is None
                else jnp.asarray(inject_mask).astype(bool))

    e_loc = jnp.arange(Ek)[:, None].repeat(NV, 1)
    v_idx = jnp.arange(NV)[None, :].repeat(Ek, 0)
    zeros_tk = jnp.zeros(Tk, jnp.int32)
    vc_iota = jnp.arange(NV, dtype=jnp.int32)
    type_iota = jnp.arange(NUM_CH_TYPES, dtype=jnp.int32)
    t_iota = jnp.arange(T, dtype=jnp.int32)

    def _sl(x, start, size):
        return jax.lax.dynamic_slice_in_dim(x, start, size, 0)

    def inject(state, t, key, rate_pkt, fl, t0):
        # full-T generation, replicated: every shard draws the identical
        # Bernoulli/destination/misroute streams (`inject.make_inject_fn`
        # verbatim), then only the local s_pkt block takes the push
        k_gen, k_dest, k_mis = jax.random.split(key, 3)
        alive = fl["term_alive"]
        gen = (jax.random.uniform(k_gen, (T,)) < rate_pkt) & inj_mask
        dest = pattern(k_dest, t).astype(jnp.int32)
        gen = gen & (dest != t_iota)
        gen = gen & alive & alive[dest]
        mis = gen_mis(k_mis, dest, state.b_count, fl)
        space = state.s_count[:T] < Q
        push = gen & space
        slot = (state.s_head[:T] + state.s_count[:T]) % Q
        new_rec = jnp.stack(
            [dest, jnp.full((T,), t, jnp.int32), mis], axis=-1)
        pushP = jnp.pad(push, (0, term_pad))
        slotP = jnp.pad(slot, (0, term_pad))
        recP = jnp.pad(new_rec, ((0, term_pad), (0, 0)))
        push_l = _sl(pushP, t0, Tk)
        idx = (jnp.arange(Tk), _sl(slotP, t0, Tk))
        rec_l = jnp.where(push_l[:, None], _sl(recP, t0, Tk),
                          state.s_pkt[idx])
        st = state.stats
        st = st.replace(generated=st.generated + gen.sum(),
                        dropped=st.dropped + (gen & ~space).sum())
        return state.replace(s_pkt=state.s_pkt.at[idx].set(rec_l),
                             s_count=state.s_count + pushP, stats=st)

    def step(state, t_key_rate_fl):
        t, key, rate_pkt, fl = t_key_rate_fl
        cached = not is_scheduled(fl)
        fl = resolve_epoch(fl, t)
        sid = jax.lax.axis_index(axis).astype(jnp.int32)
        c0, t0 = sid * Ek, sid * Tk
        with phase("inject"):
            state = inject(state, t, key, rate_pkt, fl, t0)
        # replicated counts (ghost rows stay zero), so every shard sees
        # the same global live-row census — no collective needed
        with phase("stats"):
            occ = live_rows(state)
        with phase("grant"):
            alive = jnp.pad(fl["ch_alive"], (0, ch_pad))

        # local request rows over the shard's channel/terminal blocks;
        # priorities are GLOBAL ids, so tie-breaks match everywhere
        with phase("route"):
            cid = c0 + jnp.arange(Ek, dtype=jnp.int32)
            bh_l = _sl(state.b_head, c0, Ek)
            head = state.b_pkt[(e_loc, v_idx, bh_l)].reshape(Ek * NV, -1)
            r_valid = ((_sl(state.b_count, c0, Ek) > 0).reshape(-1)
                       & (head[:, F_READY] <= t))
            if cached:
                out_b, cls_b, meta2_b = (head[:, F_OUT], head[:, F_CLS],
                                         head[:, F_META2])
            else:
                cur = ch_dst[(cid[:, None].repeat(NV, 1)).reshape(-1)]
                out_b, cls_b, meta2_b = route_kernel(
                    fl, cur, head[:, F_DEST], head[:, F_MIS],
                    head[:, F_META])
            sq = state.s_pkt[(jnp.arange(Tk), _sl(state.s_head, t0, Tk))]
            out = jnp.concatenate(
                [out_b, _sl(inject_ch, t0, Tk)]).astype(jnp.int32)
            cls = jnp.concatenate([cls_b, zeros_tk]).astype(jnp.int32)
            itime = jnp.concatenate([head[:, F_ITIME], sq[:, F_ITIME]])
            valid = jnp.concatenate(
                [r_valid, _sl(state.s_count, t0, Tk) > 0])
            prio = jnp.concatenate(
                [(cid[:, None] * NV + vc_iota[None, :]).reshape(-1),
                 Ep * NV + t0 + jnp.arange(Tk, dtype=jnp.int32)])
            rowok = valid & (out >= 0)
        # router-death reaper over the LOCAL rows (ghost rows are never
        # valid): undeliverable rows — parked on -1 OR requesting a
        # channel this epoch's fault set killed (dead eject at a dead
        # router; dead injection channel under a dead terminal's head)
        with phase("stats"):
            if reap_age:
                undel = valid & ((out < 0)
                                 | ~alive[jnp.clip(out, 0, Ep - 1)])
                reap = undel & (t - itime >= reap_age)
            else:
                undel = reap = None

        # grant: per-row credit gather (replicated tables), local
        # segment-min partials, then the [E'] pmin halo exchange
        with phase("route"):
            occ_min, occ_arg = _occ_tables(state.b_count, NC, vpc)
        with phase("grant"):
            elig_ck = (occ_min < S) | is_ej_ch[:, None]
            ok = rowok & _row_elig(elig_ck, out, cls, Ep)
            ch_ok = (state.ch_busy == 0) & alive
            seg = jnp.where(ok, out, Ep)
            if use_combined:
                key_g = jnp.where(ok, itime * R2 + prio, INF32)
                m = jax.ops.segment_min(key_g, seg, num_segments=Ep + 1)
                m = jax.lax.pmin(m[:Ep], axis)
                m = jnp.where(ch_ok, m, INF32)
                won_ch = m != INF32
                wprio = jnp.where(won_ch, m & (R2 - 1), 0)
            else:
                m1 = jax.lax.pmin(jax.ops.segment_min(
                    jnp.where(ok, itime, INF32), seg,
                    num_segments=Ep + 1)[:Ep], axis)
                # the age tie can span shards: re-mask the local rows
                # against the GLOBAL per-channel age before the prio pass
                tie = ok & (itime == m1[jnp.where(ok, out, 0)])
                m2 = jax.lax.pmin(jax.ops.segment_min(
                    jnp.where(tie, prio, INF32), seg,
                    num_segments=Ep + 1)[:Ep], axis)
                won_ch = ch_ok & (m1 != INF32)
                wprio = jnp.where(won_ch, m2, 0)

        # winner-record halo exchange: the shard owning each winning row
        # gathers its record, psum merges (losers contribute zeros)
        with phase("apply"):
            is_buf = wprio < Ep * NV
            se = wprio // NV
            sv = wprio % NV
            ts = wprio - Ep * NV
            lrow = jnp.where(is_buf, (se - c0) * NV + sv, ts - t0)
            mine = won_ch & jnp.where(is_buf,
                                      (se >= c0) & (se < c0 + Ek),
                                      (ts >= t0) & (ts < t0 + Tk))
            bclip = jnp.clip(lrow, 0, Ek * NV - 1)
            wb = head[bclip]
            ws = sq[jnp.clip(lrow, 0, Tk - 1)]
            meta2b = (wb[:, F_META2] if cached
                      else meta2_b[bclip].astype(jnp.int32))
            clsb = (wb[:, F_CLS] if cached
                    else cls_b[bclip].astype(jnp.int32))
            rec = jnp.where(
                is_buf[:, None],
                jnp.stack([wb[:, F_DEST], wb[:, F_ITIME], wb[:, F_MIS],
                           meta2b, clsb], axis=-1),
                jnp.stack([ws[:, F_DEST], ws[:, F_ITIME], ws[:, F_MIS],
                           jnp.zeros_like(ts), jnp.zeros_like(ts)],
                          axis=-1))
            w = jax.lax.psum(jnp.where(mine[:, None], rec, 0), axis)
            wdest, witime = w[:, W_DEST], w[:, W_ITIME]
            wmis, wmeta, wcls = w[:, W_MIS], w[:, W_META], w[:, W_CLS]
            wvc, wovc = _winner_vc(wcls, occ_min, occ_arg, NC, vpc)
            entered = (wmis >= 0) & (ch_dst_wg == wmis)
            wmis = jnp.where(entered, -1, wmis)
            push = won_ch & ~is_ej_ch
            vc_oh = wvc[:, None] == vc_iota[None, :]
            whead = jnp.where(vc_oh, state.b_head, 0).sum(1)
            wslot = (whead + wovc) % S

            # replicated credit/head bookkeeping, reconstructed identically
            # on every shard from the exchanged winner table
            se_m = jnp.where(won_ch & is_buf, se, Ep)
            pop1 = jnp.zeros((Ep, NV), jnp.int32).at[(se_m, sv)].add(
                1, mode="drop")
            if reap is not None:
                # reap pops: only the owning shard sees a row's reap
                # decision, but head/count state is replicated, so the reap
                # pop table is exchanged like the winner records (shards
                # own disjoint channel blocks, so psum is a concatenation)
                pop1 = pop1 + jax.lax.psum(
                    jax.lax.dynamic_update_slice_in_dim(
                        jnp.zeros((Ep, NV), jnp.int32),
                        reap[:Ek * NV].reshape(Ek, NV).astype(jnp.int32),
                        c0, axis=0), axis)
            b_head = (state.b_head + pop1) % S
            ts_m = jnp.where(won_ch & ~is_buf, ts, Tp)
            pop_s = jnp.zeros((Tp,), jnp.int32).at[ts_m].add(1, mode="drop")
            if reap is not None:
                # source-queue reap pops: like the buffer reap pops above,
                # the decision is shard-local but s_head/s_count are
                # replicated, so the pop vector is psum-exchanged (shards
                # own disjoint terminal blocks — psum is a concatenation)
                pop_s = pop_s + jax.lax.psum(
                    jax.lax.dynamic_update_slice_in_dim(
                        jnp.zeros((Tp,), jnp.int32),
                        reap[Ek * NV:].astype(jnp.int32), t0, axis=0),
                    axis)
            s_head = (state.s_head + pop_s) % Q
            s_count = state.s_count - pop_s
            b_count = (state.b_count - pop1
                       + (push[:, None] & vc_oh).astype(jnp.int32))
            ch_busy = jnp.where(won_ch, ch_ser - 1,
                                jnp.maximum(state.ch_busy - 1, 0))

        # local pushes: the shard's slice of the winner table, with the
        # route-once-per-hop evaluation on the local rows
        with phase("apply"):
            push_l = _sl(push, c0, Ek)
            wdest_l = _sl(wdest, c0, Ek)
            wmis_l = _sl(wmis, c0, Ek)
            wmeta_l = _sl(wmeta, c0, Ek)
            base = [wdest_l, _sl(witime, c0, Ek), wmis_l, wmeta_l,
                    t + _sl(ch_lat, c0, Ek)]
        if cached:
            with phase("route"):
                out2, cls2, meta2 = route_kernel(
                    fl, _sl(ch_dst, c0, Ek), wdest_l, wmis_l, wmeta_l)
                tail = [out2.astype(jnp.int32), cls2.astype(jnp.int32),
                        meta2.astype(jnp.int32)]
        else:
            z = jnp.zeros_like(wdest_l)
            tail = [z, z, z]
        with phase("apply"):
            new_rec = jnp.stack(base + tail, axis=-1)
            pe = jnp.where(push_l, jnp.arange(Ek, dtype=jnp.int32), Ek)
            b_pkt = state.b_pkt.at[
                (pe, _sl(wvc, c0, Ek), _sl(wslot, c0, Ek))].set(
                new_rec, mode="drop")

        with phase("stats"):
            st = state.stats
            w_ej = won_ch & is_ej_ch
            hops = (won_ch[:, None]
                    & (ch_type[:, None] == type_iota[None, :]))
            if reap is None:
                stranded = jax.lax.psum(
                    (valid & (out < 0)).sum().astype(jnp.int32), axis)
                reaped = st.reaped
            else:
                stranded = jax.lax.psum(
                    (undel & ~reap).sum().astype(jnp.int32), axis)
                reaped = st.reaped + jax.lax.psum(
                    reap.sum().astype(jnp.int32), axis)
            st = st.replace(
                delivered=st.delivered + w_ej.sum(),
                lat_sum=st.lat_sum + jnp.where(w_ej, t - witime, 0).sum(),
                hops=st.hops + hops.astype(jnp.int32).sum(0),
                stranded=stranded, reaped=reaped,
                occ_peak=jnp.maximum(st.occ_peak, occ))
        return state.replace(
            b_pkt=b_pkt, b_head=b_head, b_count=b_count,
            s_head=s_head, s_count=s_count, ch_busy=ch_busy,
            stats=st), None

    return step
