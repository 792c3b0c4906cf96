"""Production mesh builders (TPU v5e pods; CPU host devices in the dry-run).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization and only then builds meshes.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh

# The model code places its arrays with sharding constraints and lets
# GSPMD propagate the rest, so every mesh axis is Auto (jax.make_mesh
# defaults to Explicit axes).


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(devices: Sequence[jax.Device] | None = None) -> Mesh:
    """``devices`` (default: every local device) as a 1×N (data, model)
    mesh."""
    devices = list(devices if devices is not None else jax.devices())
    return jax.make_mesh(
        (1, len(devices)), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2, devices=devices,
    )


# TPU v5e hardware constants (per chip) — roofline denominators.
TPU_V5E = {
    "peak_bf16_flops": 197e12,   # FLOP/s
    "hbm_bandwidth": 819e9,      # B/s
    "ici_bandwidth": 50e9,       # B/s per link (~4 links usable)
    "hbm_bytes": 16 * 2**30,
}
