"""Production mesh construction.

Single pod:  (data=16, model=16)            = 256 chips (one v5e pod slice)
Multi-pod:   (pod=2, data=16, model=16)     = 512 chips

Defined as functions (never module-level constants) so importing this module
never touches JAX device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then calls these.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh", "make_fl_mesh", "HW"]


def _mk_mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk_mesh(shape, axes)


def make_local_mesh(shape=(1, 1), axes=("data", "model")) -> jax.sharding.Mesh:
    """Tiny mesh over whatever devices exist (tests / CPU smoke)."""
    return _mk_mesh(shape, axes)


def make_fl_mesh(n_devices: int) -> jax.sharding.Mesh:
    """Mesh for the sharded fused FL round (``fl/engine.py``): the selected-
    client axis shards over ``"data"``; ``"model"`` stays size 1 because the
    single-host engine replicates params (tensor parallelism inside the
    vmapped local-train step lives in ``launch/steps.py``, not here).

    On CPU, force the device count *before* any jax import with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    avail = len(jax.devices())
    if n_devices > avail:
        raise ValueError(
            f"mesh wants {n_devices} devices but only {avail} exist "
            "(on CPU set XLA_FLAGS=--xla_force_host_platform_device_count)")
    return make_local_mesh((n_devices, 1), ("data", "model"))


class HW:
    """TPU v5e hardware model used for the roofline terms (EXPERIMENTS.md)."""

    PEAK_FLOPS_BF16 = 197e12       # per chip
    HBM_BW = 819e9                 # bytes/s per chip
    ICI_BW = 50e9                  # bytes/s per link
    HBM_BYTES = 16 * 1024**3       # per chip
