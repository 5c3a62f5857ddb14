"""Production mesh construction.

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model); the ``pod`` axis
composes with ``data`` for batch sharding (DP across pods) while ``model``
(TP/EP/sequence) stays intra-pod where ICI is fastest.

Defined as a FUNCTION so importing this module never touches jax device
state; ``launch/dryrun.py`` sets xla_force_host_platform_device_count=512
before any jax import.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_debug_mesh", "make_subset_mesh",
           "POD_SHAPE", "MULTIPOD_SHAPE"]

POD_SHAPE = (16, 16)
MULTIPOD_SHAPE = (2, 16, 16)


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with sharding-in-types off: every axis is ``Auto``
    (``jax.make_mesh`` defaults to ``Explicit``)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTIPOD_SHAPE if multi_pod else POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None, model: int = 2):
    """Small mesh over however many devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    model = min(model, n)
    return _auto_mesh((n // model, model), ("data", "model"))


def make_subset_mesh(data: int, model: int = 1):
    """(data, model) mesh over the FIRST ``data * model`` devices.

    ``jax.make_mesh`` insists on covering every device; device-count scaling
    sweeps (``benchmarks/spmd_throughput.py``) need meshes over a prefix of
    the simulated host devices instead.
    """
    import numpy as np

    devs = jax.devices()
    need = data * model
    if need > len(devs):
        raise ValueError(
            f"subset mesh needs {need} devices, only {len(devs)} exist"
        )
    return jax.sharding.Mesh(
        np.asarray(devs[:need]).reshape(data, model), ("data", "model")
    )
