"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state -- the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then builds the mesh.

Meshes:
  * single-pod:  (data=16, model=16)            -- 256 chips (one v5e pod)
  * multi-pod:   (pod=2, data=16, model=16)     -- 512 chips (2 pods)

The "model" axis carries TP/EP/SP; "data" (x "pod") carries DP/ZeRO.  The
"pod" axis is the slow (DCN-ish) outer domain -- the hierarchical analogue
of the paper's single-cluster focus (DESIGN.md Sec. 6.3).
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_host_mesh", "make_launcher_mesh", "mesh_num_chips"]


def _auto_mesh(shape, axes) -> Mesh:
    # the sharding rules here are written for Auto axes (jax.make_mesh
    # defaults to Explicit)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2, pod: Optional[int] = None) -> Mesh:
    """Small mesh over whatever host devices exist (tests / examples)."""
    n = len(jax.devices())
    want = data * model * (pod or 1)
    assert n >= want, f"need {want} devices, have {n}"
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def make_launcher_mesh() -> Mesh:
    """The launchers' mesh over every visible device: a model axis of 2 once
    there are 4 or more devices (so 4 chips give (data=2, model=2))."""
    n = len(jax.devices())
    model = 2 if n >= 4 else 1
    return make_host_mesh(data=n // model, model=model)


def mesh_num_chips(mesh: Mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
