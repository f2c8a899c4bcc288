"""Production meshes.  Functions only — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).

`build_serving_mesh` is the serving entry point: it turns
`EngineConfig.mesh_shape` into a concrete device mesh whose trailing axis is
the tensor-parallel axis, and FAILS with an actionable error when the local
device count cannot cover the shape — a serving config that asked for 4
shards must never silently run mesh=1.  On CPU-only hosts the multi-device
path is emulated with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
(set before the first jax import); the CI mesh-conformance job and
tests/test_tp_mesh.py run exactly that way.
"""

from __future__ import annotations

import math

import jax
import numpy as np


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


# Axis naming per mesh rank: the trailing axis is always the TP axis.
_SERVING_AXES = {1: (), 2: ("data",), 3: ("pod", "data")}


def build_serving_mesh(
    mesh_shape: tuple[int, ...], *, tp_axis: str = "model", devices=None,
):
    """Device mesh for tensor-parallel serving (EngineConfig.mesh_shape).

    The trailing axis of `mesh_shape` is the tensor-parallel degree and is
    named `tp_axis` ("model" — the name parallel/sharding.py's rules key
    on); leading axes are named ("data",) / ("pod", "data") for replica
    dimensions.  Raises ValueError — never a silent mesh=1 — when the
    visible device count cannot supply the requested shape, with the
    CPU-emulation flag spelled out in the message."""
    shape = tuple(int(n) for n in mesh_shape)
    if not shape or any(n < 1 for n in shape):
        raise ValueError(
            f"mesh_shape must be a non-empty tuple of positive ints, "
            f"got {mesh_shape!r}"
        )
    if len(shape) > 3:
        raise ValueError(
            f"mesh_shape supports at most 3 axes, got {mesh_shape!r}"
        )
    devices = list(jax.devices()) if devices is None else list(devices)
    need = math.prod(shape)
    if need > len(devices):
        raise ValueError(
            f"mesh_shape {shape} needs {need} devices but only "
            f"{len(devices)} are visible; shrink the mesh, or (CPU "
            f"emulation) set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={need} BEFORE the first jax import"
        )
    axes = _SERVING_AXES[len(shape)] + (tp_axis,)
    return jax.sharding.Mesh(
        np.asarray(devices[:need]).reshape(shape), axes
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh_for(devices: int, *, model_parallel: int = 1):
    """Elastic mesh: largest (data, model) grid for the surviving device count.

    Used by runtime/elastic.py when a slice comes back with fewer chips."""
    model_parallel = max(1, min(model_parallel, devices))
    while devices % model_parallel:
        model_parallel -= 1
    return _make_mesh(
        (devices // model_parallel, model_parallel), ("data", "model")
    )


def data_axes(mesh) -> tuple[str, ...]:
    """Axes carrying the batch (DP/FSDP): ('pod','data') on multi-pod."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a == "model")
