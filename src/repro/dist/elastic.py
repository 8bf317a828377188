"""Elastic pool transitions: auto-tuner evictions -> DP-axis re-meshing.

The MLLess auto-tuner (``core.autotuner``, paper §4.2) decides *when* to
shrink the worker pool; this module decides *what that means* on a pod
runtime:

1. **Weak-scaling batch contract** (paper §3.2): the global batch is always
   ``B_g = P * B`` — evicting a pod shrinks the batch, it never redistributes
   the evicted pod's shard (each worker owns its slice of the dataset).
2. **Mesh schedule**: a pool of P pods trains on mesh ``(P, data, model)``;
   P == 1 drops the pod axis entirely (``mesh_shape_for``), so the single-pod
   program contains no degenerate collectives.
3. **Reintegration** (paper §4.2 eviction policy): the leaving worker's
   state is folded back in before the re-mesh —
   * replica semantics: mean-preserving model averaging
     (``reintegrate_replicas``): survivors absorb the evicted replica with
     weight 1/P_old, so the pool-mean parameter vector is unchanged;
   * error-feedback semantics (the pod path): the evicted pods' residuals
     are flushed into the shared parameters (``apply_transition``), so no
     accumulated update mass is lost across the transition.
4. **Checkpoint-mediated restore**: a transition IS a restore — save under
   the old mesh, rebuild the smaller mesh, restore with the new shardings
   (``resharded_restore`` -> ``checkpoint.store.restore_with_sharding``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.checkpoint import store as ckpt_store

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ElasticPlan:
    """Static description of an elastic training pool.

    Attributes:
      initial_pods: P at job start (the auto-tuner only ever shrinks).
      per_pod_batch: B, each pod's fixed local batch (weak scaling).
      data: within-pod data-parallel axis size.
      model: within-pod tensor/expert-parallel axis size.
      min_pods: the auto-tuner's floor (paper: never below 1).
    """

    initial_pods: int
    per_pod_batch: int
    data: int = 1
    model: int = 1
    min_pods: int = 1

    def __post_init__(self):
        if self.initial_pods < 1 or self.per_pod_batch < 1:
            raise ValueError("initial_pods and per_pod_batch must be >= 1")
        if not 1 <= self.min_pods <= self.initial_pods:
            raise ValueError(
                f"min_pods must be in [1, {self.initial_pods}], "
                f"got {self.min_pods}"
            )

    def global_batch(self, pods: int) -> int:
        """B_g = P * B — the weak-scaling contract (paper §3.2)."""
        self.validate_pool(pods)
        return pods * self.per_pod_batch

    def mesh_shape(self, pods: int) -> tuple[int, ...]:
        self.validate_pool(pods)
        return mesh_shape_for(pods, data=self.data, model=self.model)

    def mesh_axes(self, pods: int) -> tuple[str, ...]:
        self.validate_pool(pods)
        return mesh_axes_for(pods)

    def validate_pool(self, pods: int) -> None:
        if not self.min_pods <= pods <= self.initial_pods:
            raise ValueError(
                f"pool size {pods} outside "
                f"[{self.min_pods}, {self.initial_pods}]"
            )


def mesh_shape_for(pods: int, data: int = 16, model: int = 16) -> tuple[int, ...]:
    """Device-mesh shape for a pool of ``pods``; P == 1 drops the pod axis."""
    if pods < 1:
        raise ValueError(f"pods must be >= 1, got {pods}")
    if pods == 1:
        return (data, model)
    return (pods, data, model)


def mesh_axes_for(pods: int) -> tuple[str, ...]:
    """Axis names matching ``mesh_shape_for``."""
    if pods == 1:
        return ("data", "model")
    return ("pod", "data", "model")


def make_mesh_for(pods: int, data: int = 1, model: int = 1):
    """Build the jax Mesh for a pool size (delegates to launch.mesh, the
    one place meshes are constructed)."""
    from repro.launch.mesh import make_mesh

    return make_mesh(mesh_shape_for(pods, data, model), mesh_axes_for(pods))


@dataclasses.dataclass(frozen=True)
class PoolTransition:
    """One scale-in step: everything the runtime needs to re-mesh."""

    old_pods: int
    new_pods: int
    evicted: tuple[int, ...]  # pod indices leaving (highest indices first)
    old_global_batch: int
    new_global_batch: int
    old_mesh_shape: tuple[int, ...]
    new_mesh_shape: tuple[int, ...]


def plan_transition(
    plan: ElasticPlan, old_pods: int, new_pods: int
) -> PoolTransition:
    """Describe the old_pods -> new_pods shrink (evicts the top slots)."""
    plan.validate_pool(old_pods)
    plan.validate_pool(new_pods)
    if new_pods >= old_pods:
        raise ValueError(
            f"elastic transitions only shrink: {old_pods} -> {new_pods}"
        )
    return PoolTransition(
        old_pods=old_pods,
        new_pods=new_pods,
        evicted=tuple(range(new_pods, old_pods)),
        old_global_batch=plan.global_batch(old_pods),
        new_global_batch=plan.global_batch(new_pods),
        old_mesh_shape=plan.mesh_shape(old_pods),
        new_mesh_shape=plan.mesh_shape(new_pods),
    )


def transition_schedule(
    plan: ElasticPlan, pool_sizes: Sequence[int]
) -> list[PoolTransition]:
    """The full monotone shrink schedule through ``pool_sizes``.

    ``pool_sizes`` must start at ``plan.initial_pods`` and decrease; the
    auto-tuner produces exactly such a sequence (it never scales out).
    """
    sizes = list(pool_sizes)
    if not sizes or sizes[0] != plan.initial_pods:
        raise ValueError(
            f"schedule must start at initial_pods={plan.initial_pods}"
        )
    return [
        plan_transition(plan, a, b) for a, b in zip(sizes[:-1], sizes[1:])
    ]


# -- state surgery ------------------------------------------------------------


def shrink_pod_state(tree_pod: PyTree, new_pods: int) -> PyTree:
    """Keep the first ``new_pods`` slices of every (P, ...) leaf."""

    return jax.tree.map(lambda x: x[:new_pods], tree_pod)


def reintegrate_into(
    own: PyTree, leaving: PyTree, pool_before: jax.Array | float
) -> PyTree:
    """One survivor's mean-preserving pull of a leaving replica.

        x' = x + (x_leaving - x) / P_old

    Applied by every survivor, the pool-mean parameter vector is unchanged
    exactly (paper §4.2 eviction policy, mean-preserving form). This is the
    per-replica view of ``reintegrate_replicas``; the FaaS runtime's worker
    processes apply it to the flush payload a leaving peer publishes
    through the broker (``runtime.worker``).
    """
    return jax.tree.map(lambda x, l: x + (l - x) / pool_before, own, leaving)


def reintegrate_replicas(
    replicas: PyTree, evicted: int, active_mask: jax.Array
) -> PyTree:
    """Mean-preserving model averaging on eviction (replica semantics).

    The paper averages the leaving replica into every survivor; weighting
    the pull by 1/P_old keeps the pool-mean parameter vector invariant:

        x_p' = x_p + (x_evicted - x_p) / P_old
        mean_active(x') = mean_pool(x)   (exactly)

    ``replicas`` leaves have leading worker axis (P, ...); ``active_mask``
    is a bool (P,) with the evicted worker already cleared.
    """
    p_old = active_mask.shape[0]

    def leaf(x):
        leaving = jnp.broadcast_to(x[evicted][None], x.shape)
        mask = active_mask.reshape((-1,) + (1,) * (x.ndim - 1))
        pulled = reintegrate_into(x, leaving, p_old)
        return jnp.where(mask, pulled, x)

    return jax.tree.map(leaf, replicas)


def apply_transition(
    tr: PoolTransition,
    params: PyTree,
    opt_state_pod: PyTree,
    residual_pod: PyTree,
) -> tuple[PyTree, PyTree, PyTree]:
    """Error-feedback reintegration + state surgery for one shrink.

    The evicted pods' residuals are the update mass they accumulated but
    never sent; flushing them into the shared parameters is the error-
    feedback form of the paper's leaving-worker model averaging (nothing is
    lost across the re-mesh). Survivor slices of the per-pod optimizer
    state and residual are kept verbatim.
    """

    def flush(p, r):
        mass = jnp.sum(
            r[tr.new_pods:].astype(jnp.float32), axis=0
        )
        return (p.astype(jnp.float32) + mass).astype(p.dtype)

    params = jax.tree.map(flush, params, residual_pod)
    return (
        params,
        shrink_pod_state(opt_state_pod, tr.new_pods),
        shrink_pod_state(residual_pod, tr.new_pods),
    )


# -- checkpoint-mediated re-mesh ---------------------------------------------


def resharded_restore(
    directory: str,
    step: int,
    like: PyTree,
    pods: int,
    *,
    data: int = 1,
    model: int = 1,
    specs: Optional[PyTree] = None,
):
    """Restore a checkpoint under the mesh of a (possibly different) pool.

    Builds the ``mesh_shape_for(pods)`` mesh and places every leaf under a
    NamedSharding on it (replicated by default, or per-leaf ``specs``).
    This is the scale-in mechanism end-to-end: save under mesh A, shrink,
    restore under mesh B — ``jax.device_put`` reshards.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh_for(pods, data=data, model=model)
    if specs is None:
        specs = jax.tree.map(lambda _: P(), like)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return ckpt_store.restore_with_sharding(directory, step, like, shardings)
