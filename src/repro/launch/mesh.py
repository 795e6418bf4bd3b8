"""Production mesh definitions.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then calls these.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with ``Auto`` axes: the compiler propagates
    shardings from the plan's ``with_sharding_constraint`` hints (jax >= 0.9
    defaults to ``Explicit`` axes, which reject those hints)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_dev_mesh(data: int = 1, model: int = 1):
    """Small mesh for tests (requires device count >= data*model)."""
    return make_mesh((data, model), ("data", "model"))


def make_dp_mesh(data: int = 0):
    """1-D data-parallel mesh (``("data",)``) over ``data`` devices (0 =>
    all local devices) — the dp-only mesh ``steps.make_dp_train_step``
    expects (no ``model`` axis at all; the plan's activation/param helpers
    fall back to replication for the absent axis)."""
    return make_mesh((data or len(jax.devices()),), ("data",))


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (~per-direction)
VMEM_BYTES = 16 * 2 ** 20     # ~16 MiB usable
HBM_BYTES = 16 * 2 ** 30      # v5e: 16 GiB HBM
