"""The comparison that decides `correct`: it passes the program, fails the
lower-precision control, and fails a run whose timed path is broken."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, manifest, run
from test_harness import REHEARSAL, bench

from repro.core.engine import sweep as engine_sweep


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell("tiny.uniform", REHEARSAL)


def program(cell, seed, windows):
    sweep = run.make_sweep(cell, seed)
    sess = run.open_session(cell, sweep, seed, jax.devices())
    run.run_fill(sess)
    for _ in range(windows):
        sess.advance()
    return check.program_records(sess.state, sess.num_lanes), sess.cycle


@pytest.mark.parametrize("seed", [0, 2**31 - 1, 9 * 10**9 + 7])
def test_reference_agrees_and_control_fails(cell, seed):
    got, cycles = program(cell, seed, windows=8)
    seeds = cell.lane_seeds(seed)
    numbers, failed = check.compare(got, check.reference_records(
        cell.config, cell.traffic, seeds, cycles))
    assert check.verdict(numbers) and failed == 0, numbers
    control, _ = check.compare(check.reference_records(
        cell.config, cell.traffic, seeds, cycles, control=True),
        check.reference_records(cell.config, cell.traffic, seeds, cycles))
    assert not check.verdict(control), control
    assert control["lat_sum_gap"] > check.LIMITS["lat_sum_gap"]


def _unchanged(orig):
    def advance(self):
        self.cycle = min(self.cycle + self.window, self.total)
        return self.cycle
    return advance


def _half_the_lanes(orig):
    def advance(self):
        keep = jax.tree.map(jnp.copy, self.state)
        cycle = orig(self)
        half = max(self.num_lanes // 2, 1)
        self.state = jax.tree.map(lambda new, old: new.at[half:].set(
            old[half:]), self.state, keep)
        return cycle
    return advance


def _altered_answer(orig):
    def advance(self):
        cycle = orig(self)
        st = self.state.stats
        self.state = self.state.replace(stats=st.replace(
            delivered=st.delivered.at[0].add(1)))
        return cycle
    return advance


@pytest.mark.parametrize("fault", [_unchanged, _half_the_lanes,
                                   _altered_answer])
def test_broken_timed_path_is_not_correct(capsys, monkeypatch, fault):
    """Faults a one-chip cell can have: the step leaves its state as it
    was, half the lanes are left out, an answer is altered where it is
    produced.  (Its cells have no exchange between chips.)"""
    orig = engine_sweep.LaneSession.advance
    monkeypatch.setattr(engine_sweep.LaneSession, "advance", fault(orig))
    rc, line = bench(capsys, "--trace", "0", seed="77")
    assert rc == 0
    assert line["correct"] is False
    assert line["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("where,key,value", [
    ("traffic", "pattern", "bit_reverse"),
    ("routing", "route_mode", "ugal"),
    ("routing", "vc_mode", "dateline"),
    ("topology", "kind", "dragonfly"),
])
def test_reference_refuses_semantics_it_lacks(cell, where, key, value):
    """A cell the reference cannot replay is refused, naming what is
    missing, rather than compared against the wrong semantics."""
    config = json.loads(json.dumps(cell.config))
    traffic = dict(cell.traffic)
    target = traffic if where == "traffic" else config[where]
    target[key] = value
    with pytest.raises(ValueError, match=f"{key} '{value}'.*; it has"):
        check.reference_records(config, traffic, [1], 3)
