"""The harness driven end to end on the CPU rehearsal cell, and the cells
of BENCHMARK.json read by name."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import check, manifest, run
from bench import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal", "BENCHMARK.json")
FIXTURE = os.path.join(HERE, "data", "fixture.xplane.pb")
SRC = os.path.join(manifest.ROOT, "src")
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def bench(capsys, *extra, seed="3000000007", path=REHEARSAL):
    rc = run.main(["--workload", "tiny.uniform", "--seed", seed,
                   "--seconds", "0.5", *extra], manifest_path=path,
                  require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


def test_every_cell_found_by_name():
    with open(manifest.MANIFEST) as f:
        m = json.load(f)
    for w in m["workloads"]:
        cell = manifest.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.chips == w["chips"]
        assert {"pattern", "offered", "lanes", "seed_offsets", "fill",
                "window", "measure"} <= set(cell.traffic)
        assert len(cell.lane_seeds(2**33)) == cell.traffic["lanes"]
        assert len(cell.lanes(2**33)) == cell.traffic["lanes"]
        text = json.dumps([cell.config, cell.traffic])
        for knob in ("REPRO_", "step_impl", "grant_impl"):
            assert knob not in text
        # the program builds the cell's spec from the files alone; its
        # rates x seeds grid holds every lane of the cell
        spec = manifest.experiment(cell, 2**33 + 5)
        assert spec.axes.seeds == tuple(cell.lane_seeds(2**33 + 5))
        assert set(spec.axes.rates) == set(cell.lane_rates())
        assert len(spec.axes.rates) == len(set(cell.lane_rates()))
        # and a plain reference replays it
        check.require_reference(cell.config, cell.traffic)
    with pytest.raises(KeyError):
        manifest.load_cell("no-such-cell")


def test_every_metric_reader_found_by_name():
    with open(manifest.MANIFEST) as f:
        m = json.load(f)
    for metric in m["per_layer"]:
        read = manifest.metric_reader(metric["name"])
        assert callable(read)
    with pytest.raises(FileNotFoundError):
        manifest.metric_reader("no_such_metric")
    # a trace reader that finds no trace returns nothing
    for name in ("dispatch_gap_ms", "device_ms_per_lane_cycle",
                 "device_idle_share"):
        assert manifest.metric_reader(name)({}, None) is None


def test_last_line_untraced(capsys):
    rc, line = bench(capsys, "--trace", "0")
    assert rc == 0
    assert list(line) == KEYS + ["checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 2
    assert set(line["metrics"]) == {"lane_cycles_per_s", "setup_s"}
    for v in line["metrics"].values():
        assert set(v) == {"value", "unit"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    assert "breakdown" not in line
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())


def test_last_line_traced(capsys, monkeypatch):
    """The CPU has no device plane, so the reduction reads the recorded
    TPU trace in place of this run's; everything else is this run's."""
    recorded = tr.load(FIXTURE)
    monkeypatch.setattr(tr, "load", lambda path: recorded)
    rc, line = bench(capsys, "--trace", "1")
    assert rc == 0
    assert list(line) == KEYS + ["breakdown", "checks"]
    assert set(line["metrics"]) == {"compile_s", "fill_s", "dispatch_gap_ms",
                                    "device_ms_per_lane_cycle",
                                    "device_idle_share"}
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    bd = line["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10


def test_refuses_a_host_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "r16sl-g41.uniform-sat", "--seed", "1",
                  "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_refuses_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ runs nothing."""
    shutil.copy(manifest.MANIFEST, tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(manifest.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "r16sl-g41.uniform-sat",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no program" in p.stderr


def test_lane_rates_one_or_one_per_lane():
    cell = manifest.load_cell("tiny.uniform", REHEARSAL)
    assert cell.lanes(5) == [(1.0, 5), (1.0, 6)]
    per_lane = manifest.load_cell("tiny.uniform-4lane", REHEARSAL)
    assert per_lane.lanes(2**31 - 1) == [(0.4, 2**31 - 1), (0.4, 0),
                                         (0.8, 1), (0.8, 2)]
    short = dataclasses.replace(per_lane, traffic=dict(
        per_lane.traffic, offered=[0.4, 0.8]))
    with pytest.raises(ValueError, match="2 offered rates for 4"):
        short.lanes(1)


def test_four_lane_cell_on_four_host_devices():
    """A four-chip cell on four CPU devices: one lane per device through
    the program's lane shard_map, every lane correct."""
    code = (f"import sys; sys.path[:0] = [{manifest.ROOT!r}, {SRC!r}]\n"
            "from bench import run\n"
            "sys.exit(run.main(['--workload', 'tiny.uniform-4lane', "
            "'--seed', '2147483653', '--seconds', '0.5', '--trace', '0'], "
            f"manifest_path={REHEARSAL!r}, require_tpu=False))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=manifest.ROOT,
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] == 4
    assert line["device"]["count"] == 4
    assert "placement lanes:4," in p.stderr
