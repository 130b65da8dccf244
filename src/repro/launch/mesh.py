"""Production meshes. TPU v5e: 256 chips/pod, 16x16 ICI torus.

make_production_mesh is a FUNCTION so importing this module never touches jax
device state (the dry-run sets the 512-device XLA flag before first init).
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto (GSPMD propagates shardings;
    the DAP code pins them with ``with_sharding_constraint``)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int | None = None):
    """Small mesh over the actual local devices (tests, examples)."""
    n = len(jax.devices())
    data = data or (n // model)
    return _mesh((data, model), ("data", "model"))


# Hardware constants for the roofline (TPU v5e).
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
HBM_BYTES = 16 << 30          # 16 GB per chip
