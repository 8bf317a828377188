"""Self-contained pytree optimizers returning additive updates u_t."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

PyTree = Any


class OptState(NamedTuple):
    step: jax.Array  # int32, 1-indexed
    mu: PyTree  # first moment / momentum buffer (zeros pytree when unused)
    nu: PyTree  # second moment (zeros pytree when unused)


@dataclasses.dataclass(frozen=True)
class Optimizer:
    """A (init, update) pair. ``update`` returns (updates, new_state)."""

    name: str
    init: Callable[[PyTree], OptState]
    update: Callable[[PyTree, OptState, PyTree], tuple[PyTree, OptState]]


def _zeros_like_tree(params: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, params)


def _lr_at(lr: float, step: jax.Array, decay: bool) -> jax.Array:
    """Paper (Theorem 1): eta_t = eta / sqrt(t)."""
    t = jnp.maximum(step.astype(jnp.float32), 1.0)
    base = jnp.asarray(lr, jnp.float32)
    return base / jnp.sqrt(t) if decay else base


def sgd(lr: float, lr_decay: bool = False) -> Optimizer:
    """Plain SGD: u_t = -eta_t * g_t."""

    def init(params: PyTree) -> OptState:
        # two trees, not one twice: a train step donates its optimizer state
        return OptState(jnp.asarray(1, jnp.int32), _zeros_like_tree(params),
                        _zeros_like_tree(params))

    def update(grads: PyTree, state: OptState, params: PyTree):
        eta = _lr_at(lr, state.step, lr_decay)
        updates = jax.tree.map(lambda g: (-eta * g).astype(g.dtype), grads)
        return updates, OptState(state.step + 1, state.mu, state.nu)

    return Optimizer("sgd", init, update)


def nesterov(lr: float, momentum: float = 0.9, lr_decay: bool = False) -> Optimizer:
    """SGD with Nesterov momentum (paper Table 1, PMF jobs).

    m_t = beta*m_{t-1} + g_t ;  u_t = -eta * (g_t + beta*m_t)
    """

    def init(params: PyTree) -> OptState:
        # two trees, not one twice: a train step donates its optimizer state
        return OptState(jnp.asarray(1, jnp.int32), _zeros_like_tree(params),
                        _zeros_like_tree(params))

    def update(grads: PyTree, state: OptState, params: PyTree):
        eta = _lr_at(lr, state.step, lr_decay)
        mu = jax.tree.map(lambda m, g: momentum * m + g, state.mu, grads)
        updates = jax.tree.map(
            lambda g, m: (-eta * (g + momentum * m)).astype(g.dtype), grads, mu
        )
        return updates, OptState(state.step + 1, mu, state.nu)

    return Optimizer("nesterov", init, update)


def adam(
    lr: float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    lr_decay: bool = False,
    weight_decay: float = 0.0,
) -> Optimizer:
    """Adam (paper Table 1, LR jobs) with optional decoupled weight decay."""

    def init(params: PyTree) -> OptState:
        return OptState(
            jnp.asarray(1, jnp.int32),
            _zeros_like_tree(params),
            _zeros_like_tree(params),
        )

    def update(grads: PyTree, state: OptState, params: PyTree):
        t = state.step.astype(jnp.float32)
        eta = _lr_at(lr, state.step, lr_decay)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.mu, grads)
        nu = jax.tree.map(
            lambda v, g: b2 * v + (1 - b2) * jnp.square(g), state.nu, grads
        )
        bc1 = 1.0 - jnp.power(b1, t)
        bc2 = 1.0 - jnp.power(b2, t)

        def leaf(m, v, p):
            mhat = m / bc1
            vhat = v / bc2
            u = -eta * mhat / (jnp.sqrt(vhat) + eps)
            if weight_decay:
                u = u - eta * weight_decay * p
            return u.astype(p.dtype)

        updates = jax.tree.map(leaf, mu, nu, params)
        return updates, OptState(state.step + 1, mu, nu)

    return Optimizer("adam", init, update)


_REGISTRY: dict[str, Callable[..., Optimizer]] = {
    "sgd": sgd,
    "nesterov": nesterov,
    "adam": adam,
}


def make(name: str, lr: float, **kwargs) -> Optimizer:
    if name not in _REGISTRY:
        raise ValueError(f"unknown optimizer {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name](lr, **kwargs)


def apply_updates(params: PyTree, updates: PyTree) -> PyTree:
    """x_t = x_{t-1} + u_t."""
    return jax.tree.map(lambda p, u: (p + u).astype(p.dtype), params, updates)


def global_norm(tree: PyTree) -> jax.Array:
    sq = jax.tree.map(lambda g: jnp.sum(jnp.square(g.astype(jnp.float32))), tree)
    return jnp.sqrt(jax.tree.reduce(jnp.add, sq))


def clip_by_global_norm(grads: PyTree, max_norm: float) -> PyTree:
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)
