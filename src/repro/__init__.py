"""repro: reproduction of "Switch-Less Dragonfly on Wafers".

Importing any `repro` submodule runs the host-parallelism setup below
FIRST, before JAX can initialize its backend — which is the only moment
the CPU device count can still be chosen.

REPRO_HOST_DEVICES=N (opt-in) splits the host CPU into N XLA devices
(`--xla_force_host_platform_device_count=N`), which the batched sweep
engine (`repro.core.engine.sweep`) uses to `shard_map` independent sweep
lanes across devices and the experiment runner (`repro.exp.runner`) uses
to round-robin independent grid cells.  Unset (the default) leaves JAX's
single-CPU-device behavior untouched; real multi-device backends (TPU)
need no flag and shard automatically.

That knob must be read BEFORE the backend exists, hence this module.

`use_compile_cache()` places JAX's persistent compilation cache for the
entry points (`chip_smoke.py`, `python -m repro.exp.run`, the
benchmarks and examples).  It is not called on import, so importing
the library (the test suite does) writes nothing into the checkout.

This module is also the ONLY place the library reads environment
variables (`repro.analysis` lint rule REPRO002): every other `REPRO_*`
knob goes through `env_int` below (or `env_raw` for the analysis
layer's misconfiguration audits), so the full knob surface is auditable
in one file — `REPRO_SHARD_MIN_WORK` / `REPRO_CHANNEL_SHARDS` /
`REPRO_SUPERSTEP` (`core.engine.sweep`), `REPRO_COMPACT_CAP`
(`core.engine.fused`), `REPRO_REAP_AGE` (`core.engine.state`: the
router-death reaper's process-wide park-age default when
`SimConfig.reap_age` is 0), `REPRO_RR_MAX_CHANNELS` (`exp.runner`), and
`REPRO_SERVE_WINDOW` / `REPRO_SERVE_PACK` (`exp.serve.service`) document
their semantics at their call sites.
"""
from __future__ import annotations

import os
import sys
import warnings


def env_int(name: str, default: int) -> int:
    """Integer environment knob; unset/empty/non-integer -> `default`.

    The single env-read helper of the library (lint rule REPRO002 keeps
    all `os.environ` access in this module, so the knob surface stays
    auditable in one place)."""
    raw = os.environ.get(name, "").strip()
    try:
        return int(raw) if raw else default
    except ValueError:
        return default


def env_raw(name: str) -> str | None:
    """Raw environment knob string, `None` when unset.

    For the analysis layer's misconfiguration audits (CAP_PIN /
    CAP_SUPERSTEP in `analysis.capacitypass`): those findings must see
    exactly what the operator typed, not the parsed fallback `env_int`
    would silently apply — the silent fallback is the thing being
    audited.  Runtime code keeps using `env_int`."""
    return os.environ.get(name)


def _flag_setup() -> None:
    add = []
    n = os.environ.get("REPRO_HOST_DEVICES")
    if n:
        try:
            count = int(n)
        except ValueError:
            raise ValueError(
                f"REPRO_HOST_DEVICES={n!r} is not an integer device count")
        if count < 1:
            raise ValueError(f"REPRO_HOST_DEVICES={count} must be >= 1")
        add.append(f"--xla_force_host_platform_device_count={count}")
    if not add:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    # an explicit XLA_FLAGS setting of the same flag wins over the knob
    add = [f for f in add if f.split("=")[0] not in flags]
    if not add:
        return
    if "jax" in sys.modules:
        # jax may already have initialized its backend, in which case the
        # flags below are read too late and silently do nothing
        warnings.warn(
            "REPRO_HOST_DEVICES set but jax was imported before repro; "
            "the flag may not take effect",
            RuntimeWarning, stacklevel=3)
    os.environ["XLA_FLAGS"] = " ".join([flags] + add).strip()


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    nothing is overridden.  Otherwise the cache goes to the fixed path
    `<checkout>/.jax_cache` (git-ignored): a fixed path, because the
    directory is part of what a later run must find again.

    The cache key includes each op's metadata, which carries the cycle's
    phase scopes (`repro.core.spans`): JAX's default key strips it, and
    would load an executable compiled without the scopes, or with other
    ones, in place of this build's."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_flag_setup()
