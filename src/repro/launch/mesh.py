"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state. Single pod: 16x16 = 256 chips (data, model). Multi-pod: 2 pods x 256
chips with a leading "pod" axis — the slow (cross-pod ICI/DCN) dimension
that the sharding rules treat as pure data parallelism.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes, devices):
    return jax.make_mesh(
        shape, axes, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — the "
            "dry-run entrypoint must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count before any jax import"
        )
    return _mesh(shape, axes, devices[:n])


def make_smoke_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh over however many real devices exist (tests)."""
    devices = jax.devices()[: n_data * n_model]
    return _mesh((n_data, n_model), ("data", "model"), devices)


def data_axes(mesh) -> tuple:
    """Axes that shard the batch: ('pod','data') when a pod axis exists."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
