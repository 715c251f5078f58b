"""Mesh construction (functions only — importing this never touches jax
device state; jax locks the device count on first backend init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16,16)=256 chips (data, model).
    Multi-pod: (2,16,16)=512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh():
    """1-device mesh with the standard axis names (CPU tests)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_data_mesh(n_shards: int):
    """1-D pure data-parallel mesh over ``n_shards`` devices — the
    inference-engine mesh (``EngineConfig.mesh_shape``): the predictor's
    ~2M params replicate, clip batches shard over the single "data"
    axis.  CI reaches 8 CPU shards via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set before
    jax's first backend init)."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    devices = jax.devices()
    if n_shards > len(devices):
        kinds = sorted({f"{d.platform}:{d.device_kind}" for d in devices})
        raise ValueError(
            f"mesh of {n_shards} devices requested but JAX found "
            f"{len(devices)} ({', '.join(kinds)}) — run on a host with "
            f"{n_shards} accelerators, or for host-CPU devices set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_shards} "
            "before jax initializes its backend")
    return jax.make_mesh((n_shards,), ("data",),
                         axis_types=(AxisType.Auto,))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def num_chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
