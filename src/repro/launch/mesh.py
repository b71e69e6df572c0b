"""Production meshes (MULTI-POD DRY-RUN step 1).

A function, not a module constant — importing this module must never
touch jax device state.

Every mesh here uses ``AxisType.Auto`` axes: the data plane is written
for GSPMD propagation (explicit in/out shardings at jit boundaries,
``with_sharding_constraint`` anchors inside), not for the
sharding-in-types ``Explicit`` axes ``jax.make_mesh`` defaults to.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever this host actually has (tests / examples)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_serving_mesh(n_devices: int, model_parallel: int = 1,
                      devices=None):
    """The serving data plane's ("data", "model") mesh.

    ``EngineConfig.mesh_shape = (d, m)`` resolves through here (the
    single factory — the engine never calls jax.make_mesh itself):
    d*m devices, batch/KV-pages over "data", weights/LoRA-slot dout
    over "model". ``devices`` pins the mesh to an explicit device list
    (a cluster hands each replica its own slice); None takes the first
    d*m of ``jax.devices()``. CPU CI gets its devices from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    if n_devices < 1 or model_parallel < 1:
        raise ValueError(
            f"mesh shape must be positive, got n_devices={n_devices} "
            f"model_parallel={model_parallel}")
    if n_devices % model_parallel != 0:
        raise ValueError(
            f"model_parallel={model_parallel} must divide "
            f"n_devices={n_devices}")
    pool = list(jax.devices() if devices is None else devices)
    if n_devices > len(pool):
        raise ValueError(
            f"mesh wants {n_devices} devices but only {len(pool)} are "
            f"available (CPU CI: set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_devices})")
    return jax.make_mesh((n_devices // model_parallel, model_parallel),
                         ("data", "model"), axis_types=(AxisType.Auto,) * 2,
                         devices=pool[:n_devices])


def replica_devices(n_replicas: int, mesh_shape=None) -> list:
    """Disjoint device slices for ``n_replicas`` engines, each of one
    device or of a ``mesh_shape`` (data, model) mesh's d*m devices:
    replica i owns devices [i*k, (i+1)*k) of ``jax.devices()``. With
    fewer devices than that (CPU CI's single host device) every entry
    is None: the replicas share the default placement, as one host
    device forces."""
    k = 1 if mesh_shape is None else mesh_shape[0] * mesh_shape[1]
    devs = jax.devices()
    if n_replicas * k > len(devs):
        return [None] * n_replicas
    return [devs[i * k:(i + 1) * k] for i in range(n_replicas)]
