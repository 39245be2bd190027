"""The comparison that decides `correct`: it passes the program, fails the
lower-precision control, and fails a run whose timed path is broken; and
the lookup that picks the plain reference a cell is compared with."""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, manifest, run
from test_harness import HERE, REHEARSAL, bench

from repro.core.engine import sweep as engine_sweep

RECORDS = os.path.join(HERE, "data", "reference_records.json")


def rehearsal_cells() -> list:
    with open(REHEARSAL) as f:
        return [manifest.load_cell(w["name"], REHEARSAL)
                for w in json.load(f)["workloads"]]


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


def rehearsal_of(name: str):
    """The first one-chip rehearsal cell that the reference `name`
    replays."""
    mod = check.references()[name]
    for c in rehearsal_cells():
        if c.chips == 1 and check.find_reference(c.config, c.traffic) is mod:
            return c
    raise AssertionError(f"no rehearsal cell for the reference {name}")


@pytest.mark.parametrize("seed", [0, 2**31 - 1, 9 * 10**9 + 7])
@pytest.mark.parametrize("name", sorted(check.references()))
def test_reference_agrees_and_control_fails(name, seed):
    cell = rehearsal_of(name)
    got, cycles = program(cell, seed, windows=8)
    lanes = cell.lanes(seed)
    numbers, failed = check.compare(got, check.reference_records(
        cell.config, cell.traffic, lanes, cycles))
    assert check.verdict(numbers) and failed == 0, numbers
    control, _ = check.compare(check.reference_records(
        cell.config, cell.traffic, lanes, cycles, control=True),
        check.reference_records(cell.config, cell.traffic, lanes, cycles))
    assert not check.verdict(control), control
    assert control["lat_sum_gap"] > check.LIMITS["lat_sum_gap"]


def _digest(rec) -> dict:
    out = {k: int(rec[k]) for k in check.INTEGER}
    out["hops"] = [int(x) for x in rec["hops"]]
    out["lat_sum"] = float(rec["lat_sum"])
    for k in ("b_count", "s_count", "ch_busy"):
        a = np.ascontiguousarray(rec[k], dtype="<i4")
        out[k] = [list(a.shape), hashlib.sha256(a.tobytes()).hexdigest()]
    return out


def test_reference_replays_as_before(cell):
    """The moved reference gives, lane for lane and in both precisions,
    the records that the reference gave before it moved (kept as
    digests)."""
    with open(RECORDS) as f:
        known = json.load(f)
    traffic = dict(cell.traffic, fill=known["fill"])
    for control in (False, True):
        rows = [r for r in known["lanes"] if r["control"] == control]
        got = check.reference_records(
            cell.config, traffic, [(r["offered"], r["seed"]) for r in rows],
            known["cycles"], control=control)
        assert [_digest(g) for g in got] == [r["record"] for r in rows]


def test_per_lane_rates_agree_lane_by_lane():
    """Lanes of one cell at offered 0.2 and 0.6: each lane agrees with
    its own replay, and the lanes did not run at one rate."""
    cell = manifest.load_cell("tiny.uniform-rates", REHEARSAL)
    assert cell.lane_rates() == [0.2, 0.6]
    got, cycles = program(cell, 2**31 + 11, windows=4)
    want = check.reference_records(cell.config, cell.traffic,
                                   cell.lanes(2**31 + 11), cycles)
    for a, b in zip(got, want):
        numbers, failed = check.compare([a], [b])
        assert check.verdict(numbers) and failed == 0, numbers
    assert 2 * got[0]["generated"] < got[1]["generated"]
    swapped = [(rate, seed) for rate, (_, seed) in
               zip(reversed(cell.lane_rates()), cell.lanes(2**31 + 11))]
    wrong = check.reference_records(cell.config, cell.traffic, swapped,
                                    cycles)
    assert not check.verdict(check.compare(got, wrong)[0])


DUMMY = '''
from bench.references import switchless_min as base

IMPLEMENTS = dict(base.IMPLEMENTS)
USED = []


def replay(config, traffic, lanes, cycles, stats_dtype):
    USED.append(len(lanes))
    return base.replay(config, traffic, lanes, cycles, stats_dtype)
'''


def test_reference_found_by_its_declaration(capsys, monkeypatch, tmp_path):
    """A module dropped into the references directory is found by what it
    declares and replays the run; nothing else names it."""
    (tmp_path / "dropped_in.py").write_text(DUMMY)
    (tmp_path / "_helper.py").write_text("raise ImportError('not a reference')")
    monkeypatch.setattr(check, "REFERENCES", str(tmp_path))
    rc, line = bench(capsys, "--trace", "0", seed="2147483659")
    assert rc == 0 and line["correct"] is True
    assert sorted(check.references(str(tmp_path))) == ["dropped_in"]
    assert check.references(str(tmp_path))["dropped_in"].USED == [2]


def test_two_references_for_one_cell_are_refused(cell, tmp_path):
    for name in ("first", "second"):
        (tmp_path / f"{name}.py").write_text(DUMMY)
    with pytest.raises(ValueError, match="more than one.*first, second"):
        check.find_reference(cell.config, cell.traffic, str(tmp_path))


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
    ("traffic", "pattern", "worst_case"),
    ("routing", "route_mode", "ugal"),
    ("routing", "vc_mode", "dateline"),
    ("topology", "kind", "dragonfly"),
])
def test_reference_refuses_semantics_it_lacks(cell, where, key, value):
    """A cell no reference can replay is refused, naming what each
    reference lacks and has, rather than compared against the wrong
    semantics."""
    config = json.loads(json.dumps(cell.config))
    traffic = dict(cell.traffic)
    target = traffic if where == "traffic" else config[where]
    target[key] = value
    with pytest.raises(ValueError, match=f"{key} '{value}'.*; it has") as e:
        check.reference_records(config, traffic, [(1.0, 1)], 3)
    for name, mod in check.references().items():
        assert name in str(e.value)
        assert all(k in str(e.value) for k in mod.IMPLEMENTS)
