"""The trace reduction, on events with known figures and on a trace
recorded on a TPU v5e by `record_trace.py`."""
import json
import os

import pytest

from bench import manifest
from bench import trace as tr
from bench.trace import Event

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "data", "fixture.xplane.pb")
DEV = "/device:TPU:0"


def ev(plane, line, name, start, end):
    return Event(plane, line, name, start, end)


def synthetic():
    """A 10 s traced window: device 0 busy [1, 3] and [2, 4] (overlap) and
    [6, 9]; two program runs [1, 4] and [6, 9]; host in bench.block over
    [3.5, 4.5] and bench.dispatch over [4.5, 6.5]."""
    return [
        ev("/host:CPU", "python3", "bench.traced", 0.0, 10.0),
        ev("/host:CPU", "python3", "bench.block", 3.5, 4.5),
        ev("/host:CPU", "python3", "bench.dispatch", 4.5, 6.5),
        ev(DEV, tr.OPS_LINE, "fusion.1", 1.0, 3.0),
        ev(DEV, tr.OPS_LINE, "scatter.2", 2.0, 4.0),
        ev(DEV, tr.OPS_LINE, "fusion.1", 6.0, 9.0),
        ev(DEV, tr.MODULES_LINE, "jit_window", 1.0, 4.0),
        ev(DEV, tr.MODULES_LINE, "jit_window", 6.0, 9.0),
    ]


def test_leaves_drop_enclosing_ops():
    evs = [ev(DEV, tr.OPS_LINE, "while", 0.0, 10.0),
           ev(DEV, tr.OPS_LINE, "a", 1.0, 2.0),
           ev(DEV, tr.OPS_LINE, "b", 3.0, 4.0),
           ev(DEV, tr.OPS_LINE, "c", 11.0, 12.0)]
    assert [e.name for e in tr.leaves(evs)] == ["a", "b", "c"]


def test_union_merges_overlaps():
    assert tr.union([(2, 4), (1, 3), (6, 9), (9, 10)]) == [(1, 4), (6, 10)]


def test_reduce_known_figures():
    red = tr.reduce(synthetic())
    assert red["window"] == (0.0, 10.0)
    assert red["busy_s"] == pytest.approx(6.0)
    assert red["module_gaps"] == [pytest.approx(2.0)]
    assert red["op_totals"] == {"fusion.1": pytest.approx(5.0),
                                "scatter.2": pytest.approx(2.0)}
    assert red["gaps"] == [(0.0, 1.0), (4.0, 6.0), (9.0, 10.0)]
    bd = tr.breakdown(red)
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(5.0)]
    assert bd["idle_gaps"] == [["bench.dispatch", pytest.approx(2.0)],
                               ["bench.traced", pytest.approx(1.0)],
                               ["bench.traced", pytest.approx(1.0)]]


def test_busy_counts_what_a_loop_runs_not_the_loop():
    evs = synthetic() + [ev(DEV, tr.OPS_LINE, "while.3", 0.5, 9.5),
                         ev(DEV, tr.MODULES_LINE, "jit_convert", 5.0, 5.1)]
    red = tr.reduce(evs)
    assert red["busy_s"] == pytest.approx(6.0)
    assert "while.3" not in red["op_totals"]
    assert red["modules"] == 2 and red["module_gaps"] == [pytest.approx(2.0)]


def test_reduce_clips_to_traced_window():
    evs = synthetic() + [ev(DEV, tr.OPS_LINE, "late", 11.0, 12.0)]
    assert tr.reduce(evs)["busy_s"] == pytest.approx(6.0)


def test_reduce_needs_a_device_and_a_traced_span():
    with pytest.raises(ValueError):
        tr.reduce([e for e in synthetic() if e.plane != DEV])
    with pytest.raises(ValueError):
        tr.reduce([e for e in synthetic() if e.name != tr.TRACED])


def test_recorded_tpu_trace():
    """Three runs of one program with 200 ms of host sleep after each:
    three program runs, two gaps between them of about the sleep, each
    labelled bench.sleep, and device busy time close to what the host
    clock saw of the runs."""
    with open(os.path.join(HERE, "data", "fixture.json")) as f:
        known = json.load(f)
    red = tr.reduce(tr.load(FIXTURE))
    assert red["devices"] == 1
    assert red["modules"] == known["runs"]
    assert len(red["module_gaps"]) == known["runs"] - 1
    for g in red["module_gaps"]:
        assert known["sleep_s"] <= g <= known["sleep_s"] * 1.25
    longest = tr.breakdown(red)["idle_gaps"][:known["runs"]]
    assert all(lab == "bench.sleep" for lab, _ in longest)
    lo, hi = red["window"]
    assert red["busy_s"] <= sum(known["traced_block_s"])
    assert red["busy_s"] >= 0.8 * known["runs"] * known["untraced_run_s"]
    assert hi - lo >= known["runs"] * known["sleep_s"]


def test_devices_sum_for_device_time_and_average_for_idle():
    """Two devices that ran lanes, 6 s and 4 s busy, and one that ran
    nothing: device time per lane-cycle is their sum, as the per-op totals
    are; the idle share is their mean; the idle device counts in neither."""
    dev1 = "/device:TPU:1"
    evs = synthetic() + [ev(dev1, tr.OPS_LINE, "fusion.1", 2.0, 6.0),
                         ev("/device:TPU:2", tr.MODULES_LINE, "jit_x", 1, 2)]
    red = tr.reduce(evs)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx(5.0)
    assert red["busy_sum_s"] == pytest.approx(10.0)
    assert sum(red["op_totals"].values()) == pytest.approx(
        red["busy_sum_s"] + 1.0)          # the overlap on device 0
    run = {"lane_cycles_traced": 20}
    read = manifest.metric_reader
    assert read("device_ms_per_lane_cycle")(run, red) == pytest.approx(500.0)
    assert read("device_idle_share")(run, red) == pytest.approx(50.0)


def test_one_device_reads_alike_summed_or_averaged():
    """On one device, the recorded TPU trace, the sum over devices is the
    mean: device time per lane-cycle reads what it read as a mean."""
    red = tr.reduce(tr.load(FIXTURE))
    assert red["devices"] == 1
    assert red["busy_sum_s"] == red["busy_s"]
    run = {"lane_cycles_traced": 30}
    assert manifest.metric_reader("device_ms_per_lane_cycle")(run, red) == \
        1e3 * red["busy_s"] / 30
