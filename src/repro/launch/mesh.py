"""Production mesh definitions (TPU v5e).

Single pod = 16×16 = 256 chips, axes (data, model).
Multi-pod  = 2×16×16 = 512 chips, axes (pod, data, model) — the `pod` axis
carries an extra level of data parallelism across the inter-pod (DCN/ICI)
links.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state.

Every mesh here has ``Auto`` axes: the models annotate shardings with
``with_sharding_constraint`` and let XLA propagate the rest, which
``jax.make_mesh``'s default ``Explicit`` axes refuse (a table gather
without an ``out_sharding`` raises ``ShardingTypeError``).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes — the one mesh constructor of
    this repository."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import (launch/dryrun.py does this)")
    return make_mesh(shape, axes, devices=devices[:n])


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over real local devices (tests / local runs)."""
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[: data * model])


# v5e hardware constants for the roofline report
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
