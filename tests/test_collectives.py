"""shard_map collectives vs psum oracle.  Runs in a subprocess so the
multi-device CPU flag doesn't leak into the rest of the suite."""
import os
import subprocess
import sys
import textwrap

import pytest

_BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.core import collectives as C

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    x = (jnp.arange(8 * 24, dtype=jnp.float32).reshape(8, 24) * 0.37 - 11.0)

    def f(fn):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=P("data", "model"),
                                 out_specs=P("data", "model")))

    o_m = f(lambda s: jax.lax.psum(s, "model"))(x)
    o_all = f(lambda s: jax.lax.psum(s, ("model", "data")))(x)

    np.testing.assert_allclose(
        f(lambda s: C.ring_all_reduce(s, "model"))(x), o_m, rtol=1e-5)
    np.testing.assert_allclose(
        f(lambda s: C.bidir_ring_all_reduce(s, "model"))(x), o_m, rtol=1e-5)
    np.testing.assert_allclose(
        f(lambda s: C.hierarchical_psum(s, "model", "data"))(x), o_all,
        rtol=1e-5)
    np.testing.assert_allclose(
        f(lambda s: C.psum_2d(s, "model", "data"))(x), o_all, rtol=1e-5)

    # ragged leading dim (padding path)
    y = jnp.ones((8, 36), jnp.float32).cumsum(axis=1)
    o2 = f(lambda s: jax.lax.psum(s, "model"))(y)
    np.testing.assert_allclose(
        f(lambda s: C.ring_all_reduce(s, "model"))(y), o2, rtol=1e-5)
    np.testing.assert_allclose(
        f(lambda s: C.bidir_ring_all_reduce(s, "model"))(y), o2, rtol=1e-5)
    print("COLLECTIVES_OK")
""")


def test_collectives_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _BODY], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "COLLECTIVES_OK" in out.stdout
