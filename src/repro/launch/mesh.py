"""Device meshes: the production pods and the meshes one host can build.

Every mesh has ``Auto`` axes: ``ShardingRules`` places values with
``with_sharding_constraint`` hints and leaves propagation to the compiler,
which under ``Explicit`` axes (``jax.make_mesh``'s default) would be asserts.

The meshes are built by FUNCTIONS so importing this module never touches jax
device state; callers (dryrun.py, real launchers) must have set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` (dry-run) or be on
real hardware before the first call.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(n_chips: int = 1, devices=None):
    """``(data=1, model=n_chips)`` over one host's chips: one chip, or a
    four-chip host with the model sharded over all four."""
    devices = list(devices if devices is not None else jax.devices())[:n_chips]
    if len(devices) != n_chips:
        raise ValueError(f"need {n_chips} devices, have {len(devices)}")
    return _mesh((1, n_chips), ("data", "model"), devices=devices)


# TPU v5e hardware constants for the roofline model (per chip).
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s per link
