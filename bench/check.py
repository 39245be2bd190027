"""The comparison that decides a run's `correct`.

After the timed windows the program's lanes stand at some cycle.  The plain
reference (`bench.reference`) replays every lane from an empty fabric to
that cycle on the host, from the same seeds, and each lane's statistics and
in-flight state are compared with what the timed path left on the device:

    counters_off  integer counters that differ (generated, delivered,
                  dropped, stranded, reaped, the live-row high-water mark,
                  hops by channel type), summed over lanes
    lat_sum_gap   largest gap of the float32 latency sum, in cycles
    inflight_off  entries of the per-(channel, VC) buffer occupancy, the
                  source-queue occupancy and the channel busy counts that
                  differ, summed over lanes

The simulation is integer and the latency sum adds whole cycles in float32,
so every number is exact and every limit is 0.

The control (`control=True`) is the reference with its statistics one
precision lower than the configuration states: int16 counters and a
bfloat16 latency sum.
"""
from __future__ import annotations

import numpy as np

from . import reference as ref

LIMITS = {"counters_off": 0, "lat_sum_gap": 0.0, "inflight_off": 0}
INTEGER = ref.COUNTERS
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


# what the plain reference implements; anything else it would replay with
# the wrong semantics
SUPPORTED = {"topology kind": ("switchless",), "traffic pattern": ("uniform",),
             "route_mode": ("min",), "vc_mode": ("baseline",)}


def require_reference(config: dict, traffic: dict) -> None:
    """Refuse a cell whose semantics the reference does not implement."""
    asked = {"topology kind": config["topology"]["kind"],
             "traffic pattern": traffic["pattern"],
             "route_mode": config["routing"]["route_mode"],
             "vc_mode": config["routing"]["vc_mode"]}
    missing = [f"{k} {v!r}" for k, v in asked.items()
               if v not in SUPPORTED[k]]
    if missing:
        raise ValueError("the plain reference (bench/reference.py) does "
                         "not implement " + ", ".join(missing) +
                         "; it has " + "; ".join(
                             f"{k} {'/'.join(v)}" for k, v in
                             SUPPORTED.items()))


def lane_rate(config: dict, traffic: dict, fab: ref.Fabric) -> float:
    """Per-terminal packet rate of the offered flits/cycle/chip, as the
    float32 the lanes compare their variates with."""
    pkt_len = config["routing"]["pkt_len"]
    return float(np.float32(traffic["offered"] / pkt_len
                            / (fab.T / fab.chips)))


def reference_records(config: dict, traffic: dict, seeds, cycles: int,
                      control: bool = False) -> list:
    """Each lane replayed by the plain reference to `cycles`."""
    require_reference(config, traffic)
    params = dict(config["topology"]["params"])
    routing = dict(config["routing"], classes=config["vc_classes"])
    fab = ref.Fabric(**params, pkt_len=routing["pkt_len"])
    rate = lane_rate(config, traffic, fab)
    dtypes = lower_precision() if control else FULL
    out = []
    for seed in seeds:
        u, d = ref.draws(seed, cycles, fab.T)
        lane = ref.Lane(fab, vcs_per_class=routing["vcs_per_class"],
                        classes=routing["classes"],
                        buf_pkts=routing["buf_pkts"],
                        srcq_pkts=routing["srcq_pkts"], rate=rate,
                        warmup=traffic["fill"], stats_dtype=dtypes)
        for t in range(cycles):
            lane.step(t, u[t], d[t])
        out.append(lane.record())
    return out


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
