"""The readers of what the program says about itself (`bench/program.py`):
the op->phase map from HLO text, per-phase device time from a trace, the
set-up spans, and the harness driven with the new metrics on the CPU."""
import json
import os
import re

import pytest

from bench import manifest, program, run
from bench import trace as tr
from bench.trace import Event

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal", "BENCHMARK.json")
FIXTURE = os.path.join(HERE, "data", "fixture.xplane.pb")
DEV = "/device:TPU:0"
PHASE_METRICS = ["inject_ms_per_lane_cycle", "route_ms_per_lane_cycle",
                 "grant_ms_per_lane_cycle", "apply_ms_per_lane_cycle",
                 "stats_ms_per_lane_cycle", "unscoped_ms_per_lane_cycle"]
NEW = PHASE_METRICS + ["build_s", "lower_s"]

HLO = """HloModule jit_window, is_scheduled=true

%fused_computation.1 (param_0: s32[4]) -> s32[4] {
  %param_0 = s32[4]{0} parameter(0)
  ROOT %add.1 = s32[4]{0} add(%param_0, %param_0), metadata={op_type="add" op_name="jit(f)/while/body/vmap(cycle.grant)/add" source_file="x.py" source_line=3}
}

%fused_computation.5 (param_0.5: s32[4]) -> s32[4] {
  %param_0.5 = s32[4]{0} parameter(0)
  %reshape.5 = s32[4]{0} reshape(%param_0.5), metadata={op_name="jit(f)/vmap(cycle.apply)/concatenate"}
  ROOT %scatter.5 = s32[4]{0} scatter(%param_0.5, %reshape.5)
}

%fused_computation.6 (param_0.6: s32[4]) -> s32[4] {
  %param_0.6 = s32[4]{0} parameter(0)
  ROOT %fusion.7 = s32[4]{0} fusion(%param_0.6), kind=kLoop, calls=%fused_computation.1
}

ENTRY %main (p: s32[4]) -> s32[4] {
  %p = s32[4]{0} parameter(0)
  %fusion.1 = s32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_type="add" op_name="jit(f)/while/body/vmap(cycle.grant)/add"}
  %fusion.5 = s32[4]{0} fusion(%p), kind=kCustom, calls=%fused_computation.5
  %fusion.6 = s32[4]{0} fusion(%p), kind=kCustom, calls=%fused_computation.6
  %scatter.2 = s32[4]{0} scatter(%fusion.1), metadata={op_name="jit(f)/cond/vmap(cycle.route)/vmap(cycle.apply)/scatter"}
  %copy.3 = s32[4]{0} copy(%scatter.2), metadata={op_name="jit(f)/while/body/closed_call"}
  ROOT %tuple.4 = (s32[4]{0}) tuple(%copy.3)
}
"""


def test_op_phases_takes_the_innermost_scope():
    ops = program.op_phases(HLO)
    assert ops["fusion.1"] == "grant" and ops["add.1"] == "grant"
    assert ops["scatter.2"] == "apply"
    assert ops["copy.3"] is None and ops["tuple.4"] is None
    assert ops["p"] is None
    # fusions the compiler left bare: the phase of the fused computation,
    # its root's (through a nested fusion), else its ops' most common
    assert ops["fusion.6"] == "grant" and ops["fusion.5"] == "apply"
    assert ops["scatter.5"] is None
    assert "fused_computation.1" not in ops
    # without the `%` some printers leave out
    assert program.op_phases(HLO.replace("%", "")) == ops


def test_op_phases_of_a_compiled_window():
    """The window executable the program compiles on the CPU names the
    five phases, and its fusions carry one."""
    from repro.core import topology as T, traffic
    from repro.core.engine import BatchedSweep
    from repro.core.simulator import SimConfig
    net = T.build_switchless(
        T.SwitchlessParams(a=1, b=1, m=2, n=4, noc=2, g=2), "program")
    sess = BatchedSweep(net, SimConfig(warmup=10, measure=20),
                        traffic.uniform(net)).start_lanes(
        [(0.5, 1, None)], window=5)
    text = sess.compiled.as_text()
    ops = program.op_phases(text)
    assert {"inject", "route", "grant", "apply", "stats"} <= set(
        ops.values())
    assert None in ops.values()
    fusions = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = [^\n]* fusion\(",
                         text, re.M)
    assert fusions and any(ops[n] for n in fusions)


def test_phase_seconds_attributes_only_the_mapped_program():
    ops = program.op_phases(HLO)
    red = {"devices": 2, "op_totals": {
        "%fusion.1": 4.0, "%scatter.2": 2.0, "%copy.3": 1.0,
        "%reduce.9": 8.0}}          # another program's op: not attributed
    # device-seconds, summed over the two devices as busy_sum_s is
    assert program.phase_seconds(red, ops) == {
        "grant": 4.0, "apply": 2.0, "unscoped": 1.0}
    assert program.phase_seconds(red, None) is None
    assert program.phase_seconds(None, ops) is None
    assert program.phase_seconds({"devices": 1, "op_totals": {
        "%reduce.9": 1.0}}, ops) is None


def synthetic(ops_extra=()):
    """Two runs of the main program `jit_window` over [1, 4] and [6, 9],
    one run of `jit_progress` at [4.5, 4.6], and device ops: grant then
    apply with an idle gap of 0.5 inside the first run, an unscoped copy
    in the second."""
    evs = [
        Event("/host:CPU", "python3", "bench.traced", 0.0, 10.0),
        Event(DEV, tr.MODULES_LINE, "jit_window", 1.0, 4.0),
        Event(DEV, tr.MODULES_LINE, "jit_progress", 4.5, 4.6),
        Event(DEV, tr.MODULES_LINE, "jit_window", 6.0, 9.0),
        Event(DEV, tr.OPS_LINE, "%while.7", 1.0, 4.0),     # encloses
        Event(DEV, tr.OPS_LINE, "%fusion.1", 1.0, 2.0),
        Event(DEV, tr.OPS_LINE, "%scatter.2", 2.5, 4.0),
        Event(DEV, tr.OPS_LINE, "%reduce.9", 4.5, 4.6),
        Event(DEV, tr.OPS_LINE, "%fusion.1", 6.0, 7.0),
        Event(DEV, tr.OPS_LINE, "%copy.3", 7.0, 9.0),
    ]
    return evs + list(ops_extra)


def test_program_report_on_synthetic_events():
    rep = program.program_report(synthetic(), program.op_phases(HLO))
    assert rep["program"] == "jit_window" and rep["runs"] == 2
    assert rep["run_s"] == pytest.approx(6.0)
    assert rep["phase_s"] == {"grant": pytest.approx(2.0),
                              "apply": pytest.approx(1.5),
                              "unscoped": pytest.approx(2.0)}
    assert rep["unscoped_ops"] == [("%copy.3", pytest.approx(2.0))]
    assert [g[:2] for g in rep["program_gaps"]] == [
        [pytest.approx(0.5), "grant -> apply"]]
    assert rep["program_gap_s"] == pytest.approx(0.5)
    # the reduction's op totals give the same phases, less the other
    # program's op, which the map does not hold
    red = tr.reduce(synthetic())
    assert program.phase_seconds(red, program.op_phases(HLO)) == \
        pytest.approx(rep["phase_s"])


class _Exe:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


def test_phase_metrics_read_none_without_scopes(monkeypatch, capsys):
    from repro.core.engine import sweep
    bare = HLO.replace("cycle.", "scope.")
    monkeypatch.setattr(sweep, "window_executables", lambda: [_Exe(bare)])
    red = tr.reduce(synthetic())
    for name in PHASE_METRICS:
        read = manifest.metric_reader(name)
        assert read({"lane_cycles_traced": 10}, red) is None
        assert read({"lane_cycles_traced": 10}, None) is None
    assert "compile cache" in capsys.readouterr().err
    # with the scopes, the same trace reads every phase the map names
    monkeypatch.setattr(sweep, "window_executables", lambda: [_Exe(HLO)])
    got = {n: manifest.metric_reader(n)({"lane_cycles_traced": 10}, red)
           for n in PHASE_METRICS}
    assert got["grant_ms_per_lane_cycle"] == pytest.approx(200.0)
    assert got["apply_ms_per_lane_cycle"] == pytest.approx(150.0)
    assert got["unscoped_ms_per_lane_cycle"] == pytest.approx(200.0)
    assert got["inject_ms_per_lane_cycle"] == 0.0


def test_phase_metrics_read_none_from_a_program_without_them(monkeypatch):
    """A program that names no phases and keeps no spans (the commit
    before them) reads None, and nothing raises."""
    from repro.core.engine import sweep
    monkeypatch.delattr(sweep, "window_executables")
    monkeypatch.setattr(program, "span_totals", lambda: None)
    red = tr.reduce(synthetic())
    for name in NEW:
        assert manifest.metric_reader(name)(
            {"lane_cycles_traced": 10}, red) is None


def test_existing_readers_read_the_fixture_as_before():
    """The five metrics the benchmark had read the recorded TPU trace to
    the values they read before the program had phases."""
    red = tr.reduce(tr.load(FIXTURE))
    rec = {"compile_s": 1.5, "fill_s": 2.5, "lane_cycles_traced": 30}
    want = {"compile_s": 1.5, "fill_s": 2.5,
            "dispatch_gap_ms": 201.366496,
            "device_ms_per_lane_cycle": 0.523776099999998,
            "device_idle_share": 97.46948840634617}
    for name, value in want.items():
        assert manifest.metric_reader(name)(rec, red) == pytest.approx(
            value, rel=1e-12)


def test_new_metrics_in_a_traced_rehearsal_run(capsys, monkeypatch,
                                               tmp_path):
    """The rehearsal cell with the new metrics in its manifest.  The CPU
    has no device plane, so the trace is made up from the names of the
    window executable this run compiled: one second per op of each phase
    and of the unscoped ops, inside two runs of the program."""
    with open(REHEARSAL) as f:
        m = json.load(f)
    with open(manifest.MANIFEST) as f:
        entries = {e["name"]: e for e in json.load(f)["per_layer"]}
    m["per_layer"] += [entries[n] for n in NEW]
    for c in m["configs"]:
        c["file"] = os.path.join(os.path.dirname(REHEARSAL), c["file"])
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    made = {}

    def load(_):
        from repro.core.engine.sweep import window_executables
        ops = program.op_phases(window_executables()[-1].as_text())
        picked = {}
        for name, ph in ops.items():
            picked.setdefault(ph or program.UNSCOPED, name)
        evs = [Event("/host:CPU", "python3", "bench.traced", 0.0, 100.0),
               Event(DEV, tr.MODULES_LINE, "jit_window", 1.0, 50.0)]
        t = 1.0
        for ph, name in sorted(picked.items()):
            evs.append(Event(DEV, tr.OPS_LINE, "%" + name, t, t + 1.0))
            made[ph] = made.get(ph, 0.0) + 1.0
            t += 1.5
        return evs

    monkeypatch.setattr(tr, "load", load)
    rc = run.main(["--workload", "tiny.uniform", "--seed", "3000000011",
                   "--seconds", "0.5", "--trace", "1"],
                  manifest_path=str(path), require_tpu=False)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    got = line["metrics"]
    assert set(NEW) <= set(got)
    # one second of each phase: equal readings, which add up to the
    # device time per lane-cycle (the made-up ops do not overlap)
    phases = [got[n]["value"] for n in PHASE_METRICS]
    assert len(made) == len(phases) and min(phases) > 0
    assert phases == pytest.approx([phases[0]] * len(phases))
    assert sum(phases) == pytest.approx(
        got["device_ms_per_lane_cycle"]["value"])
    assert got["build_s"]["value"] > 0 and got["build_s"]["unit"] == "s"
    assert got["lower_s"]["value"] > 0
