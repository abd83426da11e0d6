"""Production mesh construction (function, not module constant — importing
this module never touches jax device state)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "make_chip_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = (data, model) = 256 chips.
    Multi-pod: (2, 16, 16) = (pod, data, model) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape: tuple, axes: tuple):
    """Mesh whose axes are all ``Auto``: the partitioner propagates shardings
    and ``with_sharding_constraint`` accepts bare ``PartitionSpec``s."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh():
    """Degenerate 1x1 mesh over the local device (smoke tests / examples)."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_chip_mesh(data: int = 1, model: int = 1, *, require_concrete: bool = False):
    """``(data, model)`` mesh for the multi-chip CiM fabric (``fabric.shard``).

    Returns a concrete device mesh when the host has ``data * model`` jax
    devices, otherwise an :class:`jax.sharding.AbstractMesh` of the same shape
    — the planning paths (``shardings.spec_for`` divisibility checks, traffic
    models) only read ``shape`` / ``axis_names``, so a 16-chip fabric can be
    sized and swept on a single-device host.

    The device-count check happens HERE, deterministically, before any jax
    mesh is built: execution paths that need real devices (the ``shard_map``
    backend of ``fabric.shard.execute_sharded_matmul``) pass
    ``require_concrete=True`` and get an immediate, actionable error instead
    of an opaque failure deep inside ``shard_map``.

    Example::

        >>> mesh = make_chip_mesh(data=2, model=2)
        >>> dict(mesh.shape)
        {'data': 2, 'model': 2}
    """
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got data={data}, model={model}")
    n_needed = data * model
    n_have = len(jax.devices())
    if n_have >= n_needed:
        return _auto_mesh((data, model), ("data", "model"))
    if require_concrete:
        raise RuntimeError(
            f"make_chip_mesh({data}, {model}) needs {n_needed} jax devices but the "
            f"host has {n_have}; run on more devices or force host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_needed}"
        )
    from jax.sharding import AbstractMesh

    return AbstractMesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
