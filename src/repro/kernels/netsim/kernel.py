"""Pallas kernels for the cycle-step arbitration hot spot.

Two entry points share one design: segment ops recast as
broadcast-compare reductions — a `[128, Ct]` one-hot of requested
channels against a channel-id iota — so there is no scatter anywhere:
everything is VPU elementwise work plus axis minima/maxima, with the
per-channel minima persisted in VMEM scratch across the grid.  All
inputs are int32; keys must stay below INF32 = 2^31 - 1.

Layout (what makes the kernels compile for the chip, not only in
interpret mode):

  rows      `[Np/128, 128]` lane-dense arrays in `(8, 128)` blocks — one
            block is R = 1024 rows.  Inside the kernel the block is
            transposed to `[128, 8]`, and column s (rows s*128 ..
            s*128+127 of the block) meets a channel tile as a `[128, 1]`
            column against a `[1, Ct]` row.
  channels  `[1, Es]` arrays in `(1, Ct)` tiles; the minima live in a
            `[Es/Ct, 1, Ct]` scratch indexed by the tile id.
  grid      `(phases, row blocks, channel tiles)`, channel tiles
            innermost, so a row block's output stays resident while it
            accumulates over the tiles.

The wrappers (`ops.py`) fold every per-row eligibility term into the
row inputs — an ineligible row's channel becomes -1, which matches no
tile — and prefetch a `[row blocks * tiles]` table into SMEM that says
which (block, tile) pairs hold a requested channel at all; the others
skip their compute.  The table only prunes pairs whose one-hot is
all-false, so the result does not depend on it.  vmap (the engine
batches lanes) adds a leading batch grid dimension via the standard
pallas batching rule; the scratch re-initialisation at the first step
of each lane keeps the lanes' accumulations independent.

`_grant_kernel` (ops.grant): the two-pass age-then-row-id grant.
Grid `(3 phases, row blocks, channel tiles)`:

  phase 0   m1[c] = min itime over rows requesting c (masked channels
            stay INF)
  phase 1   m2[c] = min row id over rows requesting c with itime == m1[c]
  phase 2   won_ch[c] = m1[c] != INF, and
            win[row] = itime == m1[out_row] and row id == m2[out_row]

`_cycle_kernel` (ops.cycle_core): the fused cycle step's grant + pop
decisions from the packed key ``itime * R2 + prio`` — (oldest age,
smallest priority) is a single lexicographic min, so one accumulation
phase replaces the two-pass chain.  Grid `(2 phases, row blocks,
channel tiles)`:

  phase 0   m[c] = min key over rows requesting c
  phase 1   after the dense busy/alive channel mask: won_ch[c] = m[c] !=
            INF, wprio[c] = m[c] & (R2-1), and win[row] = m[out_row] ==
            key_row
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# plain int (not a jnp scalar): pallas kernels may not capture array
# constants, and int32 promotion keeps the comparisons exact
INF32 = 2**31 - 1
LANES = 128          # rows per transposed column
SUBLANES = 8         # columns per row block: R = 1024 rows


def _columns(ref):
    """A `[8, 128]` row block as its 8 `[128, 1]` row columns."""
    t = ref[...].T                                        # [128, 8]
    return [t[:, s:s + 1] for s in range(SUBLANES)]


def _tile_ids(j, ct):
    return j * ct + jax.lax.broadcasted_iota(jnp.int32, (1, ct), 1)


def _col_min(cols_out, cols_val, ch, pred=None):
    """min over the block's rows of `val` where the row requests the
    tile channel (and `pred(s, out, val)` holds): `[1, Ct]`."""
    acc = jnp.full(ch.shape, INF32, jnp.int32)
    for s, (o, v) in enumerate(zip(cols_out, cols_val)):
        hit = o == ch
        if pred is not None:
            hit = hit & pred(s, o, v)
        acc = jnp.minimum(acc, jnp.min(jnp.where(hit, v, INF32), axis=0,
                                       keepdims=True))
    return acc


def _row_any(cols, hit_fn):
    """Per-row OR over the tile's channels, back in the `[8, 128]` row
    block layout: `hit_fn(s, col...)` gives the `[128, Ct]` hit mask of
    column s."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (LANES, SUBLANES), 1)
    hits = jnp.zeros((LANES, SUBLANES), jnp.int32)
    for s in range(SUBLANES):
        h = jnp.max(hit_fn(s, *(c[s] for c in cols)).astype(jnp.int32),
                    axis=1, keepdims=True)                  # [128, 1]
        hits = jnp.where(lane == s, h, hits)
    return hits.T                                           # [8, 128]


def _grant_kernel(touched_ref, out_ref, itime_ref, chok_ref,
                  win_ref, won_ref, m1_ref, m2_ref, *, ct, nct):
    phase = pl.program_id(0)
    b = pl.program_id(1)
    j = pl.program_id(2)
    ch = _tile_ids(j, ct)
    live = touched_ref[b * nct + j] != 0

    @pl.when((phase == 0) & (b == 0) & (j == 0))
    def _init():
        m1_ref[...] = jnp.full_like(m1_ref, INF32)
        m2_ref[...] = jnp.full_like(m2_ref, INF32)

    @pl.when(j == 0)
    def _clear_rows():
        win_ref[...] = jnp.zeros_like(win_ref)

    m1 = jnp.where(chok_ref[...] != 0, m1_ref[j], INF32)     # [1, Ct]
    won_ref[...] = (m1 != INF32).astype(jnp.int32)

    def rid(s):
        return (b * (SUBLANES * LANES) + s * LANES
                + jax.lax.broadcasted_iota(jnp.int32, (LANES, 1), 0))

    @pl.when((phase == 0) & live)
    def _pass_age():
        m1_ref[j] = jnp.minimum(
            m1_ref[j], _col_min(_columns(out_ref), _columns(itime_ref), ch))

    @pl.when((phase == 1) & live)
    def _pass_tiebreak():
        outs, times = _columns(out_ref), _columns(itime_ref)
        ids = [rid(s) for s in range(SUBLANES)]
        m2_ref[j] = jnp.minimum(
            m2_ref[j],
            _col_min(outs, ids, ch,
                     pred=lambda s, o, v: times[s] == m1))

    @pl.when((phase == 2) & live)
    def _emit():
        m2 = m2_ref[j]
        win_ref[...] = win_ref[...] | _row_any(
            (_columns(out_ref), _columns(itime_ref)),
            lambda s, o, t: (o == ch) & (t == m1) & (rid(s) == m2))


def grant_pallas(touched, out, itime, ch_ok, *, ct, interpret=True):
    """Raw tiled dispatch; layout and masking are ops.py's job.

    Row inputs are `[Np/128, 128]` int32 with Np a multiple of 1024
    (`out` is -1 on ineligible and padded rows); `ch_ok` is `[1, Es]`
    int32 with Es a multiple of `ct`; `touched` is the int32
    `[row blocks * Es/ct]` skip table.  Returns (win `[Np/128, 128]`,
    won_ch `[1, Es]`) int32 masks."""
    nrow, _ = out.shape
    nb = nrow // SUBLANES
    Es = ch_ok.shape[1]
    nct = Es // ct
    row = pl.BlockSpec((SUBLANES, LANES), lambda p, b, j, tc: (b, 0))
    chan = pl.BlockSpec((1, ct), lambda p, b, j, tc: (0, j))
    win, won = pl.pallas_call(
        functools.partial(_grant_kernel, ct=ct, nct=nct),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(3, nb, nct),
            in_specs=[row, row, chan],
            out_specs=[row, chan],
            scratch_shapes=[pltpu.VMEM((nct, 1, ct), jnp.int32),
                            pltpu.VMEM((nct, 1, ct), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct(out.shape, jnp.int32),
                   jax.ShapeDtypeStruct((1, Es), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
    )(touched, out, itime, ch_ok)
    return win, won


def _cycle_kernel(touched_ref, out_ref, key_ref, chok_ref,
                  win_ref, won_ref, wprio_ref, m_ref, *, ct, nct, r2):
    phase = pl.program_id(0)
    b = pl.program_id(1)
    j = pl.program_id(2)
    ch = _tile_ids(j, ct)
    live = touched_ref[b * nct + j] != 0

    @pl.when((phase == 0) & (b == 0) & (j == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, INF32)

    @pl.when(j == 0)
    def _clear_rows():
        win_ref[...] = jnp.zeros_like(win_ref)

    # dense channel mask applied after the reduction: a busy / dead /
    # padded channel (chok=0) grants nobody
    m = jnp.where(chok_ref[...] != 0, m_ref[j], INF32)        # [1, Ct]
    won = m != INF32
    won_ref[...] = won.astype(jnp.int32)
    wprio_ref[...] = jnp.where(won, m & (r2 - 1), 0)

    @pl.when((phase == 0) & live)
    def _accumulate():
        m_ref[j] = jnp.minimum(
            m_ref[j], _col_min(_columns(out_ref), _columns(key_ref), ch))

    @pl.when((phase == 1) & live)
    def _emit():
        # pop mask: keys are unique per row, so a row wins iff its key
        # equals its channel's masked minimum
        win_ref[...] = win_ref[...] | _row_any(
            (_columns(out_ref), _columns(key_ref)),
            lambda s, o, k: (o == ch) & (k == m))


def cycle_core_pallas(touched, out, key, ch_ok, *, r2, ct, interpret=True):
    """Raw tiled dispatch; layout and masking are ops.py's job.

    Row inputs are `[Np/128, 128]` int32 with Np a multiple of 1024:
    `out` is -1 on ineligible and padded rows, `key` is the packed
    ``itime * r2 + prio`` (unique over eligible rows, < INF32).  `ch_ok`
    is `[1, Es]` int32 with Es a multiple of `ct`; `touched` is the int32
    `[row blocks * Es/ct]` skip table.  Returns (win `[Np/128, 128]`,
    won_ch `[1, Es]`, wprio `[1, Es]`) int32."""
    nrow, _ = out.shape
    nb = nrow // SUBLANES
    Es = ch_ok.shape[1]
    nct = Es // ct
    row = pl.BlockSpec((SUBLANES, LANES), lambda p, b, j, tc: (b, 0))
    chan = pl.BlockSpec((1, ct), lambda p, b, j, tc: (0, j))
    win, won, wprio = pl.pallas_call(
        functools.partial(_cycle_kernel, ct=ct, nct=nct, r2=r2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(2, nb, nct),
            in_specs=[row, row, chan],
            out_specs=[row, chan, chan],
            scratch_shapes=[pltpu.VMEM((nct, 1, ct), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct(out.shape, jnp.int32),
                   jax.ShapeDtypeStruct((1, Es), jnp.int32),
                   jax.ShapeDtypeStruct((1, Es), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
    )(touched, out, key, ch_ok)
    return win, won, wprio
