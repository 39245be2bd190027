"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state.  The single-pod mesh is 16x16 = 256 chips ("data", "model"); the
multi-pod mesh is 2x16x16 = 512 chips ("pod", "data", "model").

Fabric mapping (DESIGN.md): one wafer-scale W-group hosts a pod; the
"model" axis rides the on-wafer C-group meshes, "data" the intra-W-group
local links, "pod" the global links of the switch-less Dragonfly.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the models place arrays with sharding constraints, which
    # Explicit axes (jax.make_mesh's default) do not accept
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int | None = None):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    model = model or (2 if n % 2 == 0 and n > 1 else 1)
    return _mesh((n // model, model), ("data", "model"))
