"""Production meshes.

Single pod: 16x16 = 256 chips, axes (data, model).  Multi-pod: 2 pods =
512 chips, axes (pod, data, model); the ``pod`` axis scales out with DP (or
PP via :mod:`repro.parallel.pipeline`), matching the paper's intra-rack EP +
inter-rack DP/PP layout.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["make_production_mesh", "make_rack_mesh", "make_test_mesh",
           "pctx_for_mesh"]


def _mesh(shape, axes):
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devs)} "
            "(dry-runs must set --xla_force_host_platform_device_count "
            "before jax initializes)")
    return Mesh(np.asarray(devs[:n]).reshape(shape), axes)


def make_production_mesh(*, multi_pod: bool = False, racks: int = 1):
    """256-chip pod mesh; ``racks > 1`` factors the 16-way model axis into a
    two-level (rack, model) EP topology (the paper's multi-RSN deployment)."""
    if racks > 1:
        if 16 % racks != 0:
            raise ValueError(f"racks={racks} must divide the 16-way model axis")
        shape = (16, racks, 16 // racks)
        axes = ("data", "rack", "model")
        if multi_pod:
            shape = (2, *shape)
            axes = ("pod", *axes)
        return _mesh(shape, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_rack_mesh(data: int = 1, racks: int = 2, lanes: int = 4):
    """Factored two-level EP mesh: (data, rack, model) = DP x scale-out x
    scale-up.

    The EP group is ``racks * lanes`` ranks in rack-major order (global rank
    ``g * lanes + l``), matching the flat mesh's device order so flat and
    hierarchical dispatch are bit-comparable on the same devices.  Device
    placement should map each ``model``-axis block onto one physical RSN.
    """
    return _mesh((data, racks, lanes), ("data", "rack", "model"))


def make_test_mesh(data: int = 2, model: int = 4):
    """(data, model) mesh over the first ``data * model`` devices: virtual
    CPU devices in tests, or the chips of one host."""
    return _mesh((data, model), ("data", "model"))


def pctx_for_mesh(mesh):
    from repro.models.transformer import ParallelCtx

    axes = tuple(mesh.axis_names)
    batch = tuple(a for a in axes if a not in ("model", "rack"))
    return ParallelCtx(mesh=mesh, batch_axes=batch, model_axis="model",
                       rack_axis="rack" if "rack" in axes else None)
