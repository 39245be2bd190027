"""The cycle's phase scopes and the program's host spans
(`repro.core.spans`): every step form names its phases in the compiled
window executable, the scopes change no code, and the persistent
compilation cache keeps scoped and unscoped builds apart."""
import contextlib
import os
import re

import pytest

import jax
import jax.numpy as jnp

import repro
from repro.core import spans, topology as T, traffic
from repro.core.engine import BatchedSweep, arbitrate, fused, step, sweep
from repro.core.simulator import SimConfig

FIVE = {"inject", "route", "grant", "apply", "stats"}
_SCOPE = re.compile(r'op_name="[^"]*?cycle\.([a-z]+)')
_SECTIONS = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


@pytest.fixture(scope="module")
def net():
    return T.build_switchless(
        T.SwitchlessParams(a=1, b=1, m=2, n=4, noc=2, g=3), "scopes")


def window_hlo(net, impl):
    cfg = SimConfig(warmup=20, measure=40, step_impl=impl)
    sess = BatchedSweep(net, cfg, traffic.uniform(net)).start_lanes(
        [(0.5, 1, None), (0.5, 2, None)], window=10)
    return sess.compiled.as_text()


def without_metadata(hlo: str) -> str:
    """The HLO text with each op's metadata and the stack-frame tables
    taken out: what the executable computes."""
    out, skip = [], False
    for line in hlo.splitlines():
        if line.strip() in _SECTIONS:
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            out.append(re.sub(r", metadata=\{[^{}]*\}", "", line))
    return "\n".join(out)


@pytest.mark.parametrize("impl,want", [
    ("jnp", FIVE), ("fused", FIVE), ("compact", FIVE | {"compact"})])
def test_window_hlo_names_each_phase(net, impl, want, monkeypatch):
    scoped = window_hlo(net, impl)
    assert set(_SCOPE.findall(scoped)) == want
    # the same build with every scope taken out compiles to the same ops
    for mod in (step, arbitrate, fused, sweep):
        monkeypatch.setattr(mod, "phase",
                            lambda name: contextlib.nullcontext())
    bare = window_hlo(net, impl)
    assert not _SCOPE.findall(bare)
    assert without_metadata(bare) == without_metadata(scoped)


def test_phase_refuses_an_unknown_name():
    assert spans.PHASES == ("inject", "route", "grant", "apply", "stats",
                            "compact")
    for name in spans.PHASES:
        with spans.phase(name):
            pass
    for bad in ("arbitrate", "cycle.grant", ""):
        with pytest.raises(ValueError):
            spans.phase(bad)


def test_span_totals_add_up_and_nest():
    before = spans.totals()
    with spans.span("test.outer") as outer:
        with spans.span("test.inner") as a:
            sum(range(10000))
        with spans.span("test.inner") as b:
            with spans.span("test.inner"):    # same name: counted once
                sum(range(10000))
    after = spans.totals()
    got = {k: after[k] - before.get(k, 0.0)
           for k in ("test.outer", "test.inner")}
    assert got["test.inner"] == pytest.approx(a.seconds + b.seconds)
    assert got["test.outer"] == pytest.approx(outer.seconds)
    assert 0 < got["test.inner"] <= got["test.outer"]

    @spans.span("test.decorated")
    def work(n):
        return sum(range(n))

    assert work(100) == 4950 and work.__name__ == "work"
    assert spans.totals()["test.decorated"] > 0


def test_sweep_reports_its_set_up_spans(net):
    before = spans.totals()
    cfg = SimConfig(warmup=20, measure=40, step_impl="jnp")
    sess = BatchedSweep(net, cfg, traffic.uniform(net)).start_lanes(
        [(0.5, 3, None)], window=10)
    sess.advance()
    after = spans.totals()
    grew = {k for k in after if after[k] > before.get(k, 0.0)}
    assert {"repro.build.step", "repro.build.lanes", "repro.lower",
            "repro.compile", "repro.advance"} <= grew
    lower = after["repro.lower"] - before.get("repro.lower", 0.0)
    compiled = after["repro.compile"] - before.get("repro.compile", 0.0)
    assert sess.compile_s == pytest.approx(lower + compiled, rel=1e-6)
    assert sess.compiled in sweep.window_executables()


@pytest.fixture
def cache_in(tmp_path, monkeypatch):
    """`repro.use_compile_cache()` at `tmp_path/.jax_cache`, caching
    every compile; JAX's cache settings are restored afterwards."""
    from jax._src import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_compilation_cache_include_metadata_in_key",
            "jax_persistent_cache_min_compile_time_secs")
    old = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(repro, "_CHECKOUT", str(tmp_path))
    path = repro.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    try:
        yield path
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()
        jax.clear_caches()


def _body(scoped: bool):
    def body(x):
        with spans.phase("grant") if scoped else contextlib.nullcontext():
            y = jnp.sin(x) * 2.0
        return y + 1.0
    return body


def test_compile_cache_keeps_the_scopes(cache_in):
    events = []

    def listen(name, **kw):
        events.append(name)

    jax.monitoring.register_event_listener(listen)
    x = jnp.ones(8)

    def compile_(builds):
        """For each build in turn: (cache hit?, the body's entries in the
        cache).  Every compile is made from the same line: the stack
        frames are part of the metadata, so of the key."""
        out = []
        for scoped in builds:
            jax.clear_caches()
            events.clear()
            jax.jit(_body(scoped)).lower(x).compile()
            out.append(("/jax/compilation_cache/cache_hits" in events,
                        {p for p in os.listdir(cache_in)
                         if p.startswith("jit_body")}))
        return out

    assert os.path.basename(cache_in) == ".jax_cache"
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    (hit1, scoped), (hit2, both), (hit3, again) = compile_(
        [True, False, True])
    assert not hit1 and len(scoped) == 1
    assert not hit2 and len(both) == 2 and scoped < both
    assert hit3 and again == both
    # JAX's default key strips the scopes: the unscoped build would load
    # the scoped executable in its place
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    (hit1, _), (hit2, _) = compile_([True, False])
    jax.monitoring.unregister_event_listener(listen)
    assert not hit1 and hit2
