"""Compile rehearsals for a described TPU v5e chip (no chip needed).

The TPU compiler is installed with JAX even where no chip is attached,
and it compiles for a chip that is only described.  These tests compile
the main path's Pallas kernels with ``interpret=False`` at the shapes of
the paper's published radix-16 switch-less fabric (g=41 W-groups, 1,312
chips, 30,176 channels), and the compact step's batched scan for the
`smoke` fabric, so a kernel the chip's compiler would refuse (block
tiling, scoped VMEM) fails here instead of on the chip.  Nothing runs.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.  The
persistent compilation cache is off around these compiles (a compile
for a described chip is written to it but cannot be read back).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def g41():
    """(N fused rows, N/4 compact rows, E channels) of the g=41 fabric
    under fig11's routing."""
    from repro.core.engine.fused import compact_rows, initial_capacity
    from repro.exp.registry import fig11_spec
    spec = fig11_spec(fast=False)
    net = spec.topologies[0].build()
    cfg = spec.routings[0].to_simconfig(spec.axes)
    N = compact_rows(net, cfg)
    assert net.num_channels == 30176
    return N, initial_capacity(N), net.num_channels


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("rows", ["fused", "compact"])
def test_cycle_core_compiles_at_g41(one_chip, g41, rows):
    """The fused step's grant kernel, batched over 4 lanes as the engine
    vmaps it, at the dense fused row count and the compact step's
    initial capacity rung."""
    from repro.core.engine.fused import _pow2
    from repro.kernels.netsim import cycle_core
    N_fused, N_compact, E = g41
    N = N_fused if rows == "fused" else N_compact
    B = 4

    def S(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct((B,) + shape, dtype, sharding=one_chip)

    f = jax.vmap(lambda o, t, k, c, p: cycle_core(
        o, t, k, c, r2=_pow2(N_fused), prio=p, interpret=False))
    _compile(f, S((N,)), S((N,)), S((N,), jnp.bool_), S((E,), jnp.bool_),
             S((N,)))


def test_grant_compiles_at_g41(one_chip, g41):
    """The standalone two-pass grant kernel at the dense row count."""
    from repro.kernels.netsim import grant
    N, _, E = g41

    def S(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(lambda o, t, v, ovc, ej, busy, alive: grant(
        o, t, v, ovc, ej, busy, alive, buf_pkts=8, interpret=False),
        S((N,)), S((N,)), S((N,), jnp.bool_), S((N,)), S((N,), jnp.bool_),
        S((E,)), S((E,), jnp.bool_))


def test_compact_scan_compiles_for_smoke(one_chip):
    """The compact step's whole batched scan (the sequential-lane
    dispatch form, 4 lanes) for the `smoke` fabric."""
    import dataclasses
    from repro.core.engine import BatchedSweep
    from repro.core.engine import sweep as SW
    from repro.core.engine.state import make_state
    from repro.exp import cells, get_scenario
    from repro.exp.spec import RoutingSpec, SweepAxes
    spec = get_scenario("smoke")
    spec = dataclasses.replace(
        spec, routings=(RoutingSpec(vcs_per_class=2, step_impl="compact"),),
        axes=SweepAxes(rates=(0.5, 1.5), seeds=(0, 1), warmup=50,
                       measure=200))
    cell = next(iter(cells(spec)))
    sweep = BatchedSweep(cell.net, cell.cfg, cell.pattern)
    lanes = [(r, s, None) for r in spec.axes.rates for s in spec.axes.seeds]
    _, rates, keys, data, per_lane, _ = sweep._prepare_lanes(lanes)
    state = jax.eval_shape(lambda: make_state(cell.net, cell.cfg, sweep.NV,
                                              batch=(len(lanes),)))
    shape = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=one_chip), tree)
    fn = SW._make_dispatch_fn(sweep.step, 250, 50, per_lane, None)
    compiled = fn.lower(shape(state), shape(rates), shape(keys),
                        shape(data)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
