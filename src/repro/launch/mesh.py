"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax import
to get placeholder devices; smoke tests and benches see the real single CPU.
"""
from __future__ import annotations

import jax


def auto_mesh(shape, axes):
    """`jax.make_mesh` with every axis `AxisType.Auto`: the sharding rules
    and `with_sharding_constraint` specs here are written for Auto axes
    (the installed JAX defaults `make_mesh` to Explicit ones)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_train_mesh(clients: int = 1, model: int = 1):
    """2-D client-axis × model-axis mesh for the federated engine path
    (`Experiment.with_mesh`, `repro.launch.train --mesh CxM`): the vmapped
    client dimension shards over "data", backbone params TP/FSDP-shard
    over "model"/"data" per the engine rules (docs/engines.md)."""
    return auto_mesh((int(clients), int(model)), ("data", "model"))


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *, pods: int = 0):
    """Small mesh for in-test dry-runs (requires enough host devices)."""
    if pods:
        return auto_mesh((pods, n_data, n_model), ("pod", "data", "model"))
    return auto_mesh((n_data, n_model), ("data", "model"))


def mesh_chip_count(mesh) -> int:
    return int(mesh.devices.size)
