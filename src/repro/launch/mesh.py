"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module touches no jax device state; ``dryrun.py`` sets
``xla_force_host_platform_device_count=512`` before first jax init.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# TPU v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
