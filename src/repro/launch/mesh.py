"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain placeholder devices.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """A mesh whose axes the compiler shards automatically: the sharding
    rules here name mesh axes in ``NamedSharding``s and ``with mesh:``
    blocks, which explicit axes (``jax.make_mesh``'s default) reject."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return _auto_mesh((n // model_parallel, model_parallel), ("data", "model"))


# TPU v5e roofline constants (per chip)
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW_PER_LINK = 50e9  # B/s per link
