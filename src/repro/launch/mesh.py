"""Production mesh construction.

A function, not a module-level constant — importing this module never touches
jax device state.  Single pod: (data=16, model=16) = 256 chips (TPU v5e pod
slice); multi-pod: (pod=2, data=16, model=16) = 512 chips, with the "pod"
axis crossing DCI.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "batch_axes", "HW"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch is sharded over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


class HW:
    """TPU v5e hardware constants for the roofline (per chip)."""
    PEAK_BF16_FLOPS = 197e12     # FLOP/s
    HBM_BW = 819e9               # B/s
    ICI_BW = 50e9                # B/s per link (within pod)
    DCI_BW = 25e9                # B/s effective (cross-pod, conservative)
    HBM_BYTES = 16 * 2**30       # 16 GiB per chip
