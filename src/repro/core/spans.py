"""The program's trace names: the cycle's phase scopes and host spans.

Device side: every op of a simulated cycle is traced under one
`phase(name)` scope, `jax.named_scope("cycle.<name>")`, so the compiled
executable's HLO carries the phase in each op's `op_name` metadata
(`.../vmap(cycle.grant)/scatter-min`).  The scopes change metadata only,
never the computation.  The key chain and the window's loop and cond
scaffolding stay unscoped.

    inject   packet generation and the source-queue push
    route    request rows: head gathers, route lookup, VC expansion, and
             the fused steps' route-at-push
    grant    credit check and the age-based grant (segment-min or the
             Pallas kernel; the sharded `pmin` exchange)
    apply    winner records, pushes, pops, credits, serialization
    stats    counters, occupancy census, reaper mask, the warm-up reset
    compact  the compact step's live-row partition

Host side: `span(name)` times a stretch of set-up or dispatch on the host
clock and opens a `jax.profiler.TraceAnnotation` of the same name, so a
profile shows it on the device trace's clock.  `totals()` gives the
seconds per name since the process started; a span nested in an open
span of the same name is counted once.

    repro.build.topology   the fabric's router/channel graph
    repro.build.step       step constants and routing tables (`make_step`)
    repro.build.lanes      per-lane fault data, initial state, placement
    repro.lower            tracing and lowering a sweep executable
    repro.compile          its XLA compile, or its load from the cache
    repro.advance          dispatching one window of a `LaneSession`
"""
from __future__ import annotations

import functools
import time
from collections import defaultdict

import jax

PHASES = ("inject", "route", "grant", "apply", "stats", "compact")

_TOTALS: dict = defaultdict(float)
_OPEN: dict = defaultdict(int)


def phase(name: str):
    """The named scope of one cycle phase."""
    if name not in PHASES:
        raise ValueError(f"unknown cycle phase {name!r}; valid: {PHASES}")
    return jax.named_scope("cycle." + name)


class span:
    """Host span: `with span("repro.lower") as s: ...`; afterwards
    `s.seconds` holds its duration, which `totals()` has added under its
    name.  `@span(name)` spans every call of a function."""

    def __init__(self, name: str):
        self.name = name
        self.seconds = 0.0

    def __call__(self, fn):
        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with span(self.name):
                return fn(*args, **kwargs)
        return spanned

    def __enter__(self):
        self._note = jax.profiler.TraceAnnotation(self.name)
        self._note.__enter__()
        _OPEN[self.name] += 1
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        _OPEN[self.name] -= 1
        if not _OPEN[self.name]:
            _TOTALS[self.name] += self.seconds
        self._note.__exit__(*exc)
        return False


def totals() -> dict:
    """Seconds spent in each span name so far in this process."""
    return dict(_TOTALS)
