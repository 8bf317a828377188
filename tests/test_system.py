"""End-to-end system tests: the train driver, the pod step, ISP + autotuner."""

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_train_driver_isp_end_to_end(tmp_path):
    """Train lm-8m for a few steps under ISP with checkpointing; loss is
    finite, the filter communicates < 100%, a checkpoint exists, restore
    continues."""
    from repro.launch import train as T

    ns = argparse.Namespace(
        arch="lm-8m", smoke=False, steps=6, workers=3, per_worker_batch=2,
        seq=64, mode="isp", isp_v=0.7, optimizer="adam", lr=3e-4,
        autotune=False, sched_interval=20.0,
        checkpoint_dir=str(tmp_path), checkpoint_every=3, restore=False,
        log_every=100, seed=0, out=None,
    )
    res = T.train(ns)
    assert np.isfinite(res["final_loss"])
    assert 0.0 < res["mean_sent_fraction"] < 1.0
    assert res["faas_cost_usd"] > 0
    from repro.checkpoint import store as ckpt

    assert ckpt.latest_step(str(tmp_path)) == 6

    # restore and continue (fault-tolerance path)
    ns2 = argparse.Namespace(**{**vars(ns), "restore": True, "steps": 8})
    res2 = T.train(ns2)
    assert res2["steps"] == 8


def test_train_driver_autotuner_scales_in():
    from repro.launch import train as T

    ns = argparse.Namespace(
        arch="lm-8m", smoke=False, steps=15, workers=3, per_worker_batch=2,
        seq=64, mode="bsp", isp_v=0.7, optimizer="adam", lr=3e-4,
        autotune=True, sched_interval=0.1,  # aggressive for the test
        checkpoint_dir=None, checkpoint_every=50, restore=False,
        log_every=100, seed=0, out=None,
    )
    res = T.train(ns)
    # with a flat-ish loss and an aggressive schedule, the pool must shrink
    assert res["final_pool"] <= 3


def _tiny_lm(param_dtype="bfloat16"):
    from repro.launch.train import LM_8M
    from repro.models.transformer import LM

    return LM(dataclasses.replace(
        LM_8M, name="tiny", d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
        vocab_size=512, param_dtype=param_dtype,
    ))


def _tiny_batch():
    return {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 512),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, 512),
    }


@pytest.mark.parametrize("name", ["sgd", "nesterov", "adam"])
def test_step_runs_with_each_optimizer(name):
    """The train step donates its optimizer state, so every optimizer's
    state must hold each buffer once."""
    from repro import optim
    from repro.launch.train import make_step

    lm = _tiny_lm()
    params = lm.init(jax.random.PRNGKey(0))
    x0 = np.asarray(jax.tree.leaves(params)[0], np.float32)
    optimizer = optim.make(name, 0.1)
    p1, _, _, loss, _ = make_step(lm, optimizer, None)(
        params, optimizer.init(params), None, _tiny_batch())
    assert np.isfinite(float(loss))
    assert not np.allclose(x0, np.asarray(jax.tree.leaves(p1)[0], np.float32))


@pytest.mark.parametrize("scheme", ["dense", "bitmap"])
def test_isp_step_matches_bsp_at_v0(scheme):
    """The pod step with v=0 must track plain BSP params after one step
    (Corollary 1, pod form; n_pods=1 degenerate)."""
    from repro import optim
    from repro.core.isp import ISPConfig
    from repro.dist.compression import CompressionConfig
    from repro.launch.train import lift_pod, make_pod_step, make_step

    lm = _tiny_lm()
    params = lm.init(jax.random.PRNGKey(0))
    optimizer = optim.make("sgd", 0.1)
    batch = _tiny_batch()
    # both steps donate their state: each gets its own copy
    copy = lambda t: jax.tree.map(jnp.copy, t)
    p_bsp, *_ = make_step(lm, optimizer, None)(
        copy(params), optimizer.init(params), None, batch)
    pod = make_pod_step(lm, optimizer, ISPConfig(v=0.0, decay=False),
                        CompressionConfig(scheme=scheme), 1)
    p_isp, *_ = pod(
        copy(params), lift_pod(optimizer.init(params), 1),
        lift_pod(jax.tree.map(jnp.zeros_like, params), 1), batch,
    )
    for a, b in zip(jax.tree.leaves(p_bsp), jax.tree.leaves(p_isp)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=2e-2,
                                   atol=2e-3)


@pytest.mark.parametrize("scheme", ["dense", "topk", "bitmap"])
def test_pod_step_conserves_update_mass_over_two_pods(scheme):
    """Over two pods, the pod step moves every pod's update and residual
    into params and new residuals with no mass made or lost:
    ``params' - params == sum_p sent_p`` with
    ``sent_p == r_p + u_p - r_p'``, each entry of ``sent_p`` is all of
    ``r_p + u_p`` there or nothing, and ``topk`` sends at most one entry
    of each pod's 128-entry blocks.  ``u_p`` is recomputed outside the
    step from each pod's half of the batch."""
    from repro import optim
    from repro.core.isp import ISPConfig
    from repro.dist.compression import CompressionConfig
    from repro.launch.train import lift_pod, make_pod_step
    from repro.optim import clip_by_global_norm

    lm = _tiny_lm("float32")
    optimizer = optim.make("adam", 1e-2)
    isp = ISPConfig(v=0.7)
    step = make_pod_step(lm, optimizer, isp, CompressionConfig(
        scheme=scheme, budget=0.01, block=128), 2)
    params = lm.init(jax.random.PRNGKey(0))
    opt = lift_pod(optimizer.init(params), 2)
    res = lift_pod(jax.tree.map(jnp.zeros_like, params), 2)
    # a first step leaves residuals behind; the second is the one checked
    params, opt, res, *_ = step(params, opt, res, _tiny_batch())
    copy = lambda t: jax.tree.map(jnp.copy, t)
    x0, opt0, r0 = copy(params), copy(opt), copy(res)
    batch = {k: v[::-1] for k, v in _tiny_batch().items()}
    x1, _, r1, _, sent_frac = step(params, opt, res, batch)

    def pod_update(o, b):
        grads = jax.grad(lambda p: lm.train_loss(p, b)[0])(x0)
        grads = clip_by_global_norm(grads, 1.0)
        return optimizer.update(grads, o, x0)[0]

    halves = jax.tree.map(lambda v: v.reshape((2, 2) + v.shape[1:]), batch)
    u = jax.jit(jax.vmap(pod_update))(opt0, halves)
    leaves = lambda t: [np.asarray(l, np.float64) for l in jax.tree.leaves(t)]
    n_sent = 0
    for x, x_new, u_p, r_p, r_new in zip(
            leaves(x0), leaves(x1), leaves(u), leaves(r0), leaves(r1)):
        acc = r_p + u_p
        sent = acc - r_new
        tol = 1e-6 + 1e-4 * np.abs(acc)
        np.testing.assert_allclose(x_new - x, sent.sum(0), rtol=0,
                                   atol=1e-6)
        assert (np.minimum(np.abs(sent), np.abs(acc - sent)) <= tol).all()
        kept = np.abs(sent) > tol
        n_sent += int(kept.sum())
        if scheme == "topk":
            flat = kept.reshape(2, -1)
            pad = (-flat.shape[1]) % 128
            blocks = np.pad(flat, ((0, 0), (0, pad))).reshape(2, -1, 128)
            assert (blocks.sum(-1) <= 1).all()
    assert n_sent > 0
    assert 0.0 < float(sent_frac) < 1.0
