"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state.  Dry-run entry points set XLA_FLAGS=--xla_force_host_platform_device_count=512
*before* any jax import (see dryrun.py lines 1-2).

  single pod : (data=16, model=16)            = 256 chips (TPU v5e pod)
  multi-pod  : (pod=2, data=16, model=16)     = 512 chips
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

SINGLE_POD_SHAPE = (16, 16)
SINGLE_POD_AXES = ("data", "model")
MULTI_POD_SHAPE = (2, 16, 16)
MULTI_POD_AXES = ("pod", "data", "model")

# TPU v5e hardware constants for the roofline analysis
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_LINK_BW = 50e9  # bytes/s per link


def _auto_mesh(shape, axes):
    # Auto axes: the sharding rules in repro.parallel place arrays with
    # with_sharding_constraint, which Explicit axes (make_mesh's default)
    # refuse.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = MULTI_POD_AXES if multi_pod else SINGLE_POD_AXES
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over whatever devices exist (tests / examples)."""
    if pod:
        return _auto_mesh((pod, data, model), MULTI_POD_AXES)
    return _auto_mesh((data, model), SINGLE_POD_AXES)
