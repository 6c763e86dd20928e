"""Mesh construction.

Both builders are FUNCTIONS so importing this module never touches jax
device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import to get placeholder devices.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence[jax.Device]] = None
              ) -> jax.sharding.Mesh:
    """`jax.make_mesh` with every axis `Auto`. The sharding rules place
    tensors with `with_sharding_constraint` and leave the rest to GSPMD,
    which only works on Auto axes; `jax.make_mesh` defaults to Explicit
    axes since jax 0.7. Every mesh of the repo is built here."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# TPU v5e per-chip peaks (Google Cloud documentation, "TPU v5e"): the
# roofline targets of the analytic cost model
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link
CHIP_HBM_BYTES = 16 * 2**30    # 16 GiB
