"""Layout wrappers: engine-facing entry points for the netsim kernels
(`grant` — the standalone two-pass arbitration; `cycle_core` — the
fused cycle step's packed-key grant + pop decisions).

Both fold the per-row eligibility into the row inputs (an ineligible
row requests channel -1, which no tile holds), pad the rows to whole
1024-row blocks in the lane-dense `[Np/128, 128]` layout and the
channel axis to whole tiles, build the (row block, channel tile) skip
table, and slice the masks back.  Called from inside the (jitted,
vmapped) engine step, so they are plain traceable functions — no jit
of their own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import (INF32, LANES, SUBLANES, cycle_core_pallas,
                     grant_pallas)

_BLOCK = SUBLANES * LANES   # rows per grid step
_TILE = 2048                # channels per grid step: the kernel's
                            # [128, Ct] intermediates stay ~1 MB of VMEM


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _layout(out, num_channels: int):
    """(rows, chan, touched, ct): the row padder, the channel padder,
    the skip table for the masked channel ids `out`, and the tile
    width."""
    N = out.shape[0]
    Np = _round_up(max(N, 1), _BLOCK)
    ct = min(_TILE, _round_up(num_channels, LANES))
    Es = _round_up(num_channels, ct)
    nct = Es // ct

    def rows(x, fill=0):
        x = jnp.pad(x.astype(jnp.int32), (0, Np - N),
                    constant_values=fill)
        return x.reshape(Np // LANES, LANES)

    def chan(x):
        return jnp.pad(x.astype(jnp.int32),
                       (0, Es - num_channels)).reshape(1, Es)

    tile = jnp.where(out >= 0, out // ct, nct)
    tile = jnp.pad(tile, (0, Np - N), constant_values=nct)
    touched = (tile.reshape(Np // _BLOCK, _BLOCK, 1)
               == jnp.arange(nct, dtype=jnp.int32)).any(1)
    return rows, chan, touched.astype(jnp.int32).reshape(-1), ct


def grant(out, itime, valid, ovc_count, is_eject, ch_busy, ch_alive,
          *, buf_pkts: int, interpret: bool | None = None):
    """Drop-in fused replacement for the engine's `age_based_grant` /
    `ref.grant_ref`: same arguments as the oracle, same
    (win [N] bool, won_ch [E] bool) result, one `pallas_call`.

    `interpret=None` auto-selects: compiled on TPU, interpreter elsewhere
    (the CPU path is for parity, not speed — `grant_impl="jnp"` stays the
    CPU fast path)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    N = out.shape[0]
    E = ch_busy.shape[0]
    ok = valid & (out >= 0) & ((ovc_count < buf_pkts) | is_eject)
    outm = jnp.where(ok, out, -1).astype(jnp.int32)
    rows, chan, touched, ct = _layout(outm, E)
    win, won = grant_pallas(
        touched, rows(outm, fill=-1), rows(itime),
        chan((ch_busy == 0) & ch_alive), ct=ct, interpret=interpret)
    return win.reshape(-1)[:N].astype(bool), won[0, :E].astype(bool)


def cycle_core(out, itime, ok, ch_ok, *, r2: int, prio=None,
               interpret: bool | None = None):
    """Fused-step arbitration core: one `pallas_call` computing the
    channel winner table and the per-row pop mask from the packed key
    ``itime * r2 + prio``.

    `ok` is the complete per-row eligibility (valid & routable & credit
    & alive — the fused step computes it from its cached routes; it
    implies ``out >= 0``), and `ch_ok` the dense per-channel mask (not
    busy & alive).  `prio` is the per-row tie-break priority, unique
    over ok rows; when omitted it defaults to the row iota (the dense
    fused step's tie-break — the occupancy-compacted step passes each
    active slot's GLOBAL row id so the winner ids match the oracle
    bit-for-bit).  `r2` must be a power of two > max(prio) with
    ``max(itime) * r2 + r2 - 1 < 2^31 - 1`` (the fused step checks this
    with `engine.fused.grant_form`).  Returns
    (won_ch [E] bool, wprio [E] int32 winner priority, win [N] bool).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    N = out.shape[0]
    E = ch_ok.shape[0]
    if prio is None:
        prio = jnp.arange(N, dtype=jnp.int32)
    outm = jnp.where(ok, out, -1).astype(jnp.int32)
    key = jnp.where(ok, itime * r2 + prio, INF32)
    rows, chan, touched, ct = _layout(outm, E)
    win, won, wprio = cycle_core_pallas(
        touched, rows(outm, fill=-1), rows(key, fill=INF32), chan(ch_ok),
        r2=r2, ct=ct, interpret=interpret)
    return (won[0, :E].astype(bool), wprio[0, :E],
            win.reshape(-1)[:N].astype(bool))
