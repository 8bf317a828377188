"""BSP / SSP / ISP exchange semantics (paper §3.1, §4.1, §6.4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.core import consistency as cons
from repro.core.isp import ISPConfig


def _stacked(P, key, scale=1.0):
    return {"w": scale * jax.random.normal(key, (P, 6))}


def test_bsp_everyone_sees_everything():
    P = 4
    upd = _stacked(P, jax.random.PRNGKey(0))
    visible = cons.bsp_exchange(upd)
    want = jnp.sum(upd["w"], axis=0)
    for p in range(P):
        np.testing.assert_allclose(np.asarray(visible["w"][p]),
                                   np.asarray(want), rtol=1e-6)


def test_ssp_delays_up_to_slack():
    """With slack s, an update produced at step t must be fully visible by
    step t+s; until then workers may see partial histories."""
    P, slack = 3, 2
    params = _stacked(P, jax.random.PRNGKey(1))
    state = cons.ssp_init(params, slack)
    seen = jnp.zeros_like(params["w"])
    first = _stacked(P, jax.random.PRNGKey(2))
    visible, state = cons.ssp_step(state, first)
    seen = seen + visible["w"]
    total_first = jnp.sum(first["w"], axis=0)
    zeros = _stacked(P, jax.random.PRNGKey(3), scale=0.0)
    for _ in range(slack):
        visible, state = cons.ssp_step(state, zeros)
        seen = seen + visible["w"]
    # after `slack` more steps the first step's updates are fully applied
    for p in range(P):
        np.testing.assert_allclose(np.asarray(seen[p]),
                                   np.asarray(total_first), rtol=1e-5,
                                   atol=1e-6)


def test_ssp_drain_flushes_queue():
    P, slack = 2, 3
    params = _stacked(P, jax.random.PRNGKey(4))
    state = cons.ssp_init(params, slack)
    upd = _stacked(P, jax.random.PRNGKey(5))
    visible, state = cons.ssp_step(state, upd)
    rest = cons.ssp_drain(state)
    total = visible["w"] + rest["w"]
    want = jnp.sum(upd["w"], axis=0)
    for p in range(P):
        np.testing.assert_allclose(np.asarray(total[p]), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


@given(P=st.integers(min_value=2, max_value=4),
       slack=st.integers(min_value=1, max_value=4),
       seed=st.integers(min_value=0, max_value=50))
@settings(max_examples=20, deadline=None)  # each new (P, slack) compiles
@example(P=2, slack=4, seed=15)
@example(P=4, slack=3, seed=40)
def test_ssp_visibility_bound_property(P, slack, seed):
    """SSP contract, for ANY (P, slack): an update produced at step t is
    fully visible by step t + slack."""
    params = _stacked(P, jax.random.PRNGKey(seed))
    state = cons.ssp_init(params, slack)
    first = _stacked(P, jax.random.PRNGKey(seed + 1))
    visible, state = cons.ssp_step(state, first)
    seen = np.asarray(visible["w"])
    zeros = _stacked(P, jax.random.PRNGKey(0), scale=0.0)
    for _ in range(slack):
        visible, state = cons.ssp_step(state, zeros)
        seen = seen + np.asarray(visible["w"])
    want = np.asarray(jnp.sum(first["w"], axis=0))
    for p in range(P):
        np.testing.assert_allclose(seen[p], want, rtol=1e-5, atol=1e-6)


@given(P=st.integers(min_value=2, max_value=4),
       slack=st.integers(min_value=1, max_value=4),
       n_steps=st.integers(min_value=1, max_value=5),
       seed=st.integers(min_value=0, max_value=50))
@settings(max_examples=20, deadline=None)  # each new (P, slack) compiles
@example(P=4, slack=2, n_steps=5, seed=19)
@example(P=2, slack=4, n_steps=2, seed=39)
def test_ssp_drain_conserves_mass_property(P, slack, n_steps, seed):
    """The delay queue never loses or duplicates update mass: everything
    made visible across the steps plus ``ssp_drain``'s remainder equals
    the sum of every update fed in, per replica row."""
    params = _stacked(P, jax.random.PRNGKey(seed))
    state = cons.ssp_init(params, slack)
    total_in = np.zeros((P, 6), np.float32)
    total_seen = np.zeros((P, 6), np.float32)
    for k in range(n_steps):
        upd = _stacked(P, jax.random.PRNGKey(seed + 10 + k))
        total_in = total_in + np.asarray(jnp.sum(upd["w"], axis=0))
        visible, state = cons.ssp_step(state, upd)
        total_seen = total_seen + np.asarray(visible["w"])
    rest = cons.ssp_drain(state)
    total_seen = total_seen + np.asarray(rest["w"])
    for p in range(P):
        np.testing.assert_allclose(total_seen[p], total_in[p],
                                   rtol=1e-4, atol=1e-5)


def test_isp_exchange_bounds_divergence():
    """Replica divergence under ISP stays within the Theorem 1 bound: any
    two replicas differ by at most the sum of the P residual bounds."""
    P = 3
    key = jax.random.PRNGKey(6)
    params = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (P,) + x.shape),
        {"w": jax.random.normal(key, (10,))},
    )
    cfg = ISPConfig(v=0.5, decay=False)
    state = cons.isp_init(params)
    for step in range(6):
        upd = {"w": 0.1 * jax.random.normal(jax.random.PRNGKey(10 + step),
                                            (P, 10))}
        # the significance test runs against the PRE-exchange replica
        # values; the residual bound |r_i| <= v * max(|x_i|, floor) holds
        # relative to these, not to the post-step params
        w_at_test = np.asarray(params["w"])
        visible, state, masks = cons.isp_exchange(cfg, state, upd, params)
        params = jax.tree.map(lambda p, v: p + v, params, visible)
    w = np.asarray(params["w"])
    # x_p - x_q == r_p - r_q exactly (emitted mass is common to all
    # replicas), so the spread is bounded by the P per-worker residual
    # bounds evaluated where the filter evaluated them
    spread = np.abs(w.max(0) - w.min(0))
    bound = P * 0.5 * np.maximum(np.abs(w_at_test).max(0), 1e-8) + 1e-5
    assert np.all(spread <= bound), (spread, bound)


def test_isp_v0_equals_bsp_replicas_identical():
    P = 4
    key = jax.random.PRNGKey(7)
    params = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (P,) + x.shape),
        {"w": jax.random.normal(key, (8,))},
    )
    cfg = ISPConfig(v=0.0, decay=False)
    state = cons.isp_init(params)
    for step in range(4):
        upd = {"w": 0.1 * jax.random.normal(jax.random.PRNGKey(20 + step),
                                            (P, 8))}
        visible, state, _ = cons.isp_exchange(cfg, state, upd, params)
        params = jax.tree.map(lambda p, v: p + v, params, visible)
    w = np.asarray(params["w"])
    for p in range(1, P):
        np.testing.assert_allclose(w[p], w[0], rtol=1e-5, atol=1e-6)


def test_isp_communicates_less_than_bsp():
    P = 4
    key = jax.random.PRNGKey(8)
    params = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (P,) + x.shape),
        {"w": jax.random.normal(key, (1000,))},
    )
    cfg = ISPConfig(v=2.0, decay=False)
    state = cons.isp_init(params)
    upd = {"w": 0.01 * jax.random.normal(jax.random.PRNGKey(30), (P, 1000))}
    _, state, masks = cons.isp_exchange(cfg, state, upd, params)
    frac = float(
        jnp.mean(jnp.asarray([jnp.mean(m.astype(jnp.float32))
                              for m in jax.tree.leaves(masks)]))
    )
    assert frac < 0.5
