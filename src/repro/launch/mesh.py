"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
nothing here does that globally.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """jax.make_mesh with Auto axis types (its default is Explicit, which
    ``with_sharding_constraint`` refuses)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_ingest_mesh(n_devices: int):
    """1-D ``("data",)`` mesh for sharded multi-stream ingest
    (DESIGN.md §13): each device owns a disjoint block of stream slots.

    Unlike ``make_production_mesh`` (fixed 256/512-chip shapes), this
    takes any ``n_devices`` and validates it against the runtime device
    count up front, so a bad count fails with an actionable error instead
    of an opaque XLA one deep inside the first sharded dispatch. The mesh
    is built over the *first* ``n_devices`` devices: on a TPU host these
    are real chips, and CPU CI builds 1/2/4-device meshes inside one
    8-device ``--xla_force_host_platform_device_count`` process.

    Module contract preserved: device state is only touched when this is
    *called*, never at import.
    """
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    avail = jax.device_count()
    if n_devices > avail:
        raise ValueError(
            f"make_ingest_mesh(n_devices={n_devices}) but only {avail} "
            f"jax device(s) are visible; on an accelerator host the mesh "
            f"takes real devices, so run on one with {n_devices}; on CPU "
            f"only, set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_devices} in the "
            f"environment BEFORE the first jax import (see the "
            f"sharded-ingest CI step)")
    import numpy as np
    return jax.sharding.Mesh(np.array(jax.devices()[:n_devices]), ("data",))
