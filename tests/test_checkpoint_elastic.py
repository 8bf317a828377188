"""Checkpoint/restore + elastic re-mesh + fault-tolerance paths."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import store as ckpt


def _tree(key):
    k1, k2 = jax.random.split(key)
    return {
        "params": {"w": jax.random.normal(k1, (8, 4)),
                   "layers": [jax.random.normal(k2, (3,)),
                              jnp.ones((2, 2), jnp.bfloat16)]},
        "step_count": jnp.asarray(7, jnp.int32),
    }


def test_save_restore_roundtrip(tmp_path):
    tree = _tree(jax.random.PRNGKey(0))
    path = ckpt.save(str(tmp_path), 7, tree, extra={"pool": 5})
    assert os.path.exists(os.path.join(path, "manifest.json"))
    out = ckpt.restore(str(tmp_path), 7, tree)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert ckpt.manifest_extra(str(tmp_path), 7)["pool"] == 5


def test_latest_step_and_atomicity(tmp_path):
    tree = _tree(jax.random.PRNGKey(1))
    assert ckpt.latest_step(str(tmp_path)) is None
    ckpt.save(str(tmp_path), 10, tree)
    ckpt.save(str(tmp_path), 20, tree)
    # a leftover tmp dir (simulated crash mid-write) must be ignored
    os.makedirs(os.path.join(str(tmp_path), "step_0000000030.tmp"))
    assert ckpt.latest_step(str(tmp_path)) == 20


def test_restore_shape_mismatch_raises(tmp_path):
    tree = _tree(jax.random.PRNGKey(2))
    ckpt.save(str(tmp_path), 1, tree)
    bad = jax.tree.map(lambda x: jnp.zeros((5, 5)), tree)
    with pytest.raises((ValueError, KeyError)):
        ckpt.restore(str(tmp_path), 1, bad)


def test_overwrite_same_step(tmp_path):
    t1 = _tree(jax.random.PRNGKey(3))
    t2 = jax.tree.map(lambda x: x + 1 if x.dtype != jnp.int32 else x, t1)
    ckpt.save(str(tmp_path), 5, t1)
    ckpt.save(str(tmp_path), 5, t2)
    out = ckpt.restore(str(tmp_path), 5, t1)
    np.testing.assert_allclose(
        np.asarray(out["params"]["w"]), np.asarray(t2["params"]["w"]),
        rtol=1e-6,
    )


def test_train_restore_continues_bit_exact(tmp_path):
    """Train k steps, checkpoint, train k more; vs restore + k more —
    identical parameters (the node-failure recovery guarantee)."""
    from repro import optim
    from repro.data.tokens import TokenPipeline
    from repro.launch.specs import opt_state_defs
    from repro.launch.train import LM_8M, make_step
    from repro.models import params as pdefs
    from repro.models.transformer import LM
    import dataclasses

    cfg = dataclasses.replace(LM_8M, name="lm-tiny", d_model=64, n_heads=4,
                              n_kv_heads=4, d_ff=128, vocab_size=512)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    o_defs = opt_state_defs(lm.param_defs())
    opt_state = jax.tree.map(
        lambda d: jnp.zeros(d.shape, d.dtype) if d.init == "zeros"
        else jnp.ones(d.shape, d.dtype), o_defs, is_leaf=pdefs.is_def)
    # the step donates params and optimizer state: branch A steps on the
    # arrays themselves, and the restore reads only their shapes
    step = make_step(lm, optim.make("adam", 1e-3), None)
    pipe = TokenPipeline(cfg.vocab_size, 32, 2, seed=0)

    for i in range(3):
        params, opt_state, *_ = step(params, opt_state, None,
                                     pipe.next_batch(i))
    like = {"p": params, "o": opt_state}
    ckpt.save(str(tmp_path), 3, like)

    # branch A: continue in-process
    pa, oa = params, opt_state
    for i in range(3, 6):
        pa, oa, *_ = step(pa, oa, None, pipe.next_batch(i))

    # branch B: restore (simulated restart) then continue
    restored = ckpt.restore(str(tmp_path), 3, like)
    pb, ob = restored["p"], restored["o"]
    for i in range(3, 6):
        pb, ob, *_ = step(pb, ob, None, pipe.next_batch(i))

    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_elastic_restore_with_resharding(tmp_path):
    """Save under one sharding, restore under another (the scale-in
    transition). On 1 CPU device both meshes are trivial, but the API path
    — restore_with_sharding -> device_put per leaf — is the real one."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh

    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    ckpt.save(str(tmp_path), 2, tree)
    mesh = make_mesh((1,), ("data",))
    shardings = {"w": NamedSharding(mesh, P("data", None))}
    out = ckpt.restore_with_sharding(str(tmp_path), 2, tree, shardings)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(tree["w"]))
    assert out["w"].sharding == shardings["w"]


def test_elastic_pool_transitions():
    """dist.elastic: pool-size schedule maps onto meshes and the weak-
    scaling batch contract B_g = P * B holds across transitions."""
    from repro.dist import elastic

    plan = elastic.ElasticPlan(initial_pods=4, per_pod_batch=8)
    assert plan.global_batch(4) == 32
    assert plan.global_batch(2) == 16
    sizes = [elastic.mesh_shape_for(p, data=2, model=2) for p in (4, 2, 1)]
    assert sizes[0] == (4, 2, 2)
    assert sizes[1] == (2, 2, 2)
    assert sizes[2] == (2, 2)  # pod axis dropped at 1
