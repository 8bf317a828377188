"""Production mesh construction.

IMPORTANT: functions, not module-level constants — importing this module must
never touch jax device state (the dry-run sets XLA_FLAGS before first init;
smoke tests must keep seeing 1 CPU device).

Mesh axes:
  * ``pod``   — pure data parallelism across pods/slices; the slow (DCI)
    axis. This is the MLLess *worker* axis: the ISP significance filter
    compresses gradient exchange across it (DESIGN.md §2).
  * ``data``  — within-pod data parallel + FSDP (params/optimizer sharded).
  * ``model`` — tensor/expert parallel + sequence parallel for activations.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mk(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's target mesh: 16x16 single pod (256 chips) or
    2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (elastic pool sizes, CPU smoke meshes)."""
    return _mk(shape, axes)


def make_elastic_mesh(n_pods: int, data: int = 16, model: int = 16):
    """Mesh for a scaled-in pool of ``n_pods`` pods (the auto-tuner's
    transition target). n_pods == 1 drops the pod axis entirely; the
    shape/axes schedule is owned by ``dist.elastic.mesh_shape_for``."""
    from repro.dist.elastic import mesh_axes_for, mesh_shape_for

    return make_mesh(
        mesh_shape_for(n_pods, data, model), mesh_axes_for(n_pods)
    )


def has_axis(mesh, name: str) -> bool:
    return name in mesh.axis_names


def batch_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of this mesh, outermost first."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
