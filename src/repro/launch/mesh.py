"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax
initialization and only then calls these.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """Auto axes: shardings are propagated by the compiler from the
    parameters' placements and ``with_sharding_constraint`` — the model
    code carries no explicit-sharding annotations."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Whatever this host has — used by tests/examples on CPU."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return _mesh((n // model_axis, model_axis), ("data", "model"))


# TPU v5e hardware constants for the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # bytes/s
ICI_BW = 50e9                  # bytes/s per link (~uni-directional)
HBM_PER_CHIP = 16 * 2**30      # bytes
