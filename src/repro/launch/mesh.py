"""Production meshes. A FUNCTION (not a module-level constant) so importing
never touches jax device state."""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod: 16x16 = 256 chips ("data", "model"); multi-pod adds a leading
    "pod" axis (2 pods = 512 chips). "pod" composes with "data" for DP/FSDP."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Single-process test mesh over whatever devices exist (1 on CPU)."""
    n = len(jax.devices())
    return _make_mesh((1, n), ("data", "model"))
