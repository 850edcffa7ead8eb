"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the `pod` axis composes
with `data` for batch/FSDP sharding (gradient all-reduce crosses the DCN).

Defined as functions (never module-level constants) so importing this
module touches no jax device state.
"""

from __future__ import annotations

import dataclasses

import jax


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis `Auto` (compiler-chosen sharding
    propagation, which the model code's sharding rules assume)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    dp_axes: tuple      # axes batch/FSDP shard over (includes "pod")
    tp_axis: str
    dp_size: int
    tp_size: int


def mesh_axes(mesh) -> MeshAxes:
    names = mesh.axis_names
    tp_axis = "model"
    dp_axes = tuple(n for n in names if n != tp_axis)
    dp_size = 1
    for n in dp_axes:
        dp_size *= mesh.shape[n]
    return MeshAxes(dp_axes=dp_axes, tp_axis=tp_axis,
                    dp_size=dp_size, tp_size=mesh.shape[tp_axis])
