"""The comparison that decides a run's `correct`.

After the timed windows the program's lanes stand at some cycle.  A plain
reference replays every lane from an empty fabric to that cycle on the
host, from the same seeds and offered rates, and each lane's statistics
and in-flight state are compared with what the timed path left on the
device:

    counters_off  integer counters that differ (generated, delivered,
                  dropped, stranded, reaped, the live-row high-water mark,
                  hops by channel type), summed over lanes
    lat_sum_gap   largest gap of the float32 latency sum, in cycles
    inflight_off  entries of the per-(channel, VC) buffer occupancy, the
                  source-queue occupancy and the channel busy counts that
                  differ, summed over lanes

The simulation is integer and the latency sum adds whole cycles in float32,
so every number is exact and every limit is 0.

The plain references are the modules of `bench/references/` (a file whose
name starts with `_` is a helper, not a reference).  Each declares the
cells it implements in `IMPLEMENTS`, a dict from a dotted path into
{"config": <configuration file>, "traffic": <traffic file>} to the values
it replays there, and replays lanes through
`replay(config, traffic, lanes, cycles, stats_dtype)`: `lanes` are
(offered, seed) pairs, and it returns one record per lane with the keys
`compare` reads.  A cell is replayed by the one module whose declaration
covers it; none, or more than one, refuses the cell.  So new semantics
bring a new module and no edit here.

The control (`control=True`) is the reference with its statistics one
precision lower than the configuration states: int16 counters and a
bfloat16 latency sum.
"""
from __future__ import annotations

import functools
import glob
import importlib.util
import os

import numpy as np

REFERENCES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "references")
LIMITS = {"counters_off": 0, "lat_sum_gap": 0.0, "inflight_off": 0}
INTEGER = ("generated", "delivered", "dropped", "stranded", "reaped",
           "occ_peak")
FULL = (np.int32, np.float32)


def lower_precision():
    import ml_dtypes
    return (np.int16, ml_dtypes.bfloat16)


def program_records(state, lanes: int) -> list:
    """The first `lanes` lanes of a session's `SimState`, on the host."""
    st = state.stats
    host = {k: np.asarray(getattr(st, k)) for k in
            INTEGER + ("hops", "lat_sum")}
    arrays = {k: np.asarray(getattr(state, k))
              for k in ("b_count", "s_count", "ch_busy")}
    out = []
    for i in range(lanes):
        rec = {k: int(host[k][i]) for k in INTEGER}
        rec["hops"] = host["hops"][i].astype(np.int64)
        rec["lat_sum"] = float(host["lat_sum"][i])
        rec.update({k: v[i] for k, v in arrays.items()})
        out.append(rec)
    return out


def references(where: str | None = None) -> dict:
    """{name: module} of the plain references in the directory `where`
    (default `REFERENCES`)."""
    return _load(where or REFERENCES)


@functools.cache
def _load(where: str) -> dict:
    """Each module of `where` whose file name does not start with `_`."""
    out = {}
    for path in sorted(glob.glob(os.path.join(where, "*.py"))):
        name = os.path.basename(path)[:-3]
        if name.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            f"bench_reference_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[name] = mod
    return out


def _field(cell: dict, path: str):
    for key in path.split("."):
        if not isinstance(cell, dict) or key not in cell:
            return None
        cell = cell[key]
    return cell


def _has(mod) -> str:
    return ", ".join(f"{k} {'/'.join(map(str, v))}"
                     for k, v in mod.IMPLEMENTS.items())


def find_reference(config: dict, traffic: dict, where: str | None = None):
    """The one module of `where` (default `REFERENCES`) whose declaration
    covers the cell.  Refuses a cell that none covers, naming what each
    lacks and has, and one that several cover."""
    where = where or REFERENCES
    found = _load(where)
    cell = {"config": config, "traffic": traffic}
    fits, lacks = [], []
    for name, mod in found.items():
        off = [f"{k} {_field(cell, k)!r}" for k, allowed in
               mod.IMPLEMENTS.items() if _field(cell, k) not in allowed]
        if off:
            lacks.append(f"{name} does not implement {', '.join(off)}; "
                         f"it has {_has(mod)}")
        else:
            fits.append(name)
    if not fits:
        raise ValueError(f"no plain reference in {where} replays this "
                         "cell: " + " | ".join(lacks or ["none found"]))
    if len(fits) > 1:
        raise ValueError(f"more than one plain reference in {where} "
                         f"covers this cell: {', '.join(fits)}")
    return found[fits[0]]


def require_reference(config: dict, traffic: dict) -> None:
    """Refuse a cell whose semantics no plain reference implements."""
    find_reference(config, traffic)


def reference_records(config: dict, traffic: dict, lanes, cycles: int,
                      control: bool = False) -> list:
    """Each lane, an (offered, seed) pair, replayed to `cycles` by the
    plain reference that covers the cell."""
    mod = find_reference(config, traffic)
    dtypes = lower_precision() if control else FULL
    return mod.replay(config, traffic, list(lanes), cycles, dtypes)


def compare(got: list, want: list) -> tuple:
    """(numbers, failed lanes): the three numbers over all lanes, and how
    many lanes differ in any of them."""
    counters = inflight = failed = 0
    gap = 0.0
    for a, b in zip(got, want):
        c = sum(a[k] != b[k] for k in INTEGER) + int(
            (np.asarray(a["hops"]) != np.asarray(b["hops"])).sum())
        f = sum(int((np.asarray(a[k]) != np.asarray(b[k])).sum())
                if np.shape(a[k]) == np.shape(b[k]) else int(np.size(b[k]))
                for k in ("b_count", "s_count", "ch_busy"))
        g = abs(float(a["lat_sum"]) - float(b["lat_sum"]))
        counters, inflight, gap = counters + c, inflight + f, max(gap, g)
        failed += bool(c or f or g)
    failed += abs(len(got) - len(want))
    return ({"counters_off": int(counters), "lat_sum_gap": gap,
             "inflight_off": int(inflight)}, failed)


def verdict(numbers: dict) -> bool:
    return all(numbers[k] <= lim for k, lim in LIMITS.items())


def lines(numbers: dict) -> list:
    """Each compared number beside its limit, one per line."""
    return [f"check {k}={numbers[k]} limit={LIMITS[k]}" for k in LIMITS]


def as_json(numbers: dict) -> dict:
    return {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
