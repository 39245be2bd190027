"""The trace reduction, on events with known figures and on a trace
recorded on a TPU v5e by `record_trace.py`."""
import json
import os

import pytest

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
