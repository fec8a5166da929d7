"""Production mesh construction.

Defined as functions (not module constants) so importing never touches
jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import to fabricate the device pool; real deployments get the same mesh
from the actual TPU topology.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


# TPU v5e-ish hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
