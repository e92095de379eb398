"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state - the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any JAX
initialization and only then calls these.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``.  The sharding layer
    (:mod:`repro.distributed.sharding`) places arrays with
    ``with_sharding_constraint``, which refuses the ``Explicit`` axes that
    ``jax.make_mesh`` defaults to in JAX 0.9; every mesh of the repo is
    built here."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(shape),
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 dual-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host has (CPU smoke / tests): pure data-parallel."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))
