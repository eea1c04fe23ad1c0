"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): the single-pod mesh is (data=16, model=16) = 256 chips
(one TPU v5e pod); the multi-pod mesh adds a leading "pod" axis =
(2, 16, 16) = 512 chips.  At 1000+ nodes the pod axis simply grows — "pod"
and "data" are both batch axes, so no model code changes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """A 1-device mesh for CPU smoke tests (axis names preserved)."""
    return jax.make_mesh((1, 1), ("data", "model"))


def make_switch_mesh(n_devices: int | None = None, *, devices=None):
    """1-D ``("switch",)`` mesh for the sharded fragment fleet.

    Fragment rows of the fleet param table / window stacks partition over
    this axis (see docs/sharding.md).  ``n_devices`` defaults to every
    visible device; pass a smaller count (or an explicit ``devices``
    sequence) to build sub-meshes — e.g. a 1-device mesh for the
    sharded-vs-single-device parity tests.  ``jax.make_mesh`` takes the
    first ``n_devices`` of ``jax.devices()`` when the product is smaller
    than the device count, so this works under
    ``--xla_force_host_platform_device_count=N`` without slicing here.

    The axis is ``AxisType.Auto``: the fleet commits its window stacks
    with explicit ``NamedSharding``s and runs the query merge under
    ``shard_map``, and every other op on a sharded stack (the parity
    patch's ``.at[].set``, epoch gathers) is left to the compiler's
    sharding propagation.
    """
    n = len(devices) if devices is not None else (
        len(jax.devices()) if n_devices is None else int(n_devices))
    return jax.make_mesh((n,), ("switch",), devices=devices,
                         axis_types=(AxisType.Auto,))


def switch_axis_size(mesh) -> int:
    """Shard count of the fleet's ``switch`` axis (1 if absent)."""
    return mesh.shape["switch"] if "switch" in mesh.axis_names else 1


def data_axis_size(mesh) -> int:
    size = 1
    for name in ("pod", "data"):
        if name in mesh.axis_names:
            size *= mesh.shape[name]
    return size
