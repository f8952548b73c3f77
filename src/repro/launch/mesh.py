"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device; only
launch/dryrun.py forces 512 host devices).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh", "HW"]


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """A device mesh with ``Auto`` (GSPMD) axes.  The model stack places
    data with sharding constraints, which ``jax.make_mesh``'s default
    ``Explicit`` axes reject (e.g. the expert gather in
    ``models.ffn.moe_layer_ep``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """Single-device mesh with the production axis names (CPU tests)."""
    n = len(jax.devices())
    return make_mesh((1, n, 1), ("pod", "data", "model"))


class HW:
    """TPU v5e-class hardware constants for the roofline (per chip)."""

    PEAK_FLOPS_BF16 = 197e12  # FLOP/s
    HBM_BW = 819e9  # B/s
    ICI_BW = 50e9  # B/s per link (roofline uses per-chip link bandwidth)
    HBM_BYTES = 16 * 1024**3  # 16 GiB
