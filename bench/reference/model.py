"""The reference decoder, its initial weights from a seed, and its steps.

A configuration file (``bench/configs/<name>.json``) gives the sizes under
``run`` and the block kinds under ``blocks``; this module builds the model
from those alone.  The parameter tree has the layout and leaf order of the
published model as the program stores it (a stacked layer axis, sorted
names), so the weights drawn from a seed are the same draw: the seed's key
is split into one key per leaf in that order, and a weight matrix is a
standard normal scaled by 1/sqrt(its second-to-last dimension).

``readings`` follows the first steps of a training run and returns what the
benchmark compares: each step's loss, each leaf's clipped first gradient
norm, and each leaf's change after the last step.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import tied_embedding, update

PyTree = Any


@dataclasses.dataclass(frozen=True)
class Leaf:
    shape: tuple
    init: str  # normal | zeros | ones


def _kind(name: str):
    return importlib.import_module(f"bench.reference.{name}")


def layout(run: dict, blocks: dict) -> PyTree:
    d, f = run["hidden_size"], run["intermediate_size"]
    L = run["num_hidden_layers"]
    norm = _kind(blocks["norm"])
    block = {
        "ff": _kind(blocks["ff"]).defs(d, f),
        "mixer": _kind(blocks["mixer"]).defs(
            d, run["num_attention_heads"], run["num_key_value_heads"],
            run["head_dim"]),
        "norm1": norm.defs(d),
        "norm2": norm.defs(d),
    }
    stacked = {part: {k: Leaf((L,) + shape, init)
                      for k, (shape, init) in leaves.items()}
               for part, leaves in block.items()}
    flat = lambda t: {k: Leaf(shape, init) for k, (shape, init) in t.items()}
    return {"embed": flat(tied_embedding.defs(run["vocab_size"], d)),
            "final_norm": flat(norm.defs(d)),
            "groups": [{"b0": stacked}]}


def _leaves(tree):
    return jax.tree.flatten(tree, is_leaf=lambda x: isinstance(x, Leaf))


def _draw(leaf: Leaf, key) -> jax.Array:
    if leaf.init == "zeros":
        return jnp.zeros(leaf.shape, jnp.float32)
    if leaf.init == "ones":
        return jnp.ones(leaf.shape, jnp.float32)
    fan_in = leaf.shape[-2] if len(leaf.shape) >= 2 else leaf.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.maximum(fan_in, 1)).astype(jnp.float32)
    return scale * jax.random.normal(key, leaf.shape, jnp.float32)


def init(run: dict, blocks: dict, seed: int, store) -> PyTree:
    """The weights drawn from ``seed``, stored in ``store``, in one call."""
    flat, tree = _leaves(layout(run, blocks))

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(flat))
        return tree.unflatten([_draw(l, k).astype(store)
                               for l, k in zip(flat, keys)])

    return make(jax.random.PRNGKey(seed))


def leaf_names(run: dict, blocks: dict) -> list[str]:
    paths, _ = jax.tree_util.tree_flatten_with_path(
        layout(run, blocks), is_leaf=lambda x: isinstance(x, Leaf))
    return [jax.tree_util.keystr(p) for p, _ in paths]


def loss_fn(run: dict, blocks: dict, num, params: PyTree, tokens, labels):
    norm = _kind(blocks["norm"])
    ff = _kind(blocks["ff"])
    mixer = _kind(blocks["mixer"])
    heads = dict(n_heads=run["num_attention_heads"],
                 n_kv=run["num_key_value_heads"], head_dim=run["head_dim"],
                 rope_base=run["rope_theta"])

    @jax.checkpoint
    def layer(p, x):
        x = x + mixer.apply(p["mixer"], norm.apply(p["norm1"], x), num, **heads)
        return x + ff.apply(p["ff"], norm.apply(p["norm2"], x), num)

    x = tied_embedding.embed(params["embed"], tokens, num)
    stack = params["groups"][0]["b0"]
    for i in range(run["num_hidden_layers"]):
        x = layer(jax.tree.map(lambda a: a[i], stack), x)
    h = norm.apply(params["final_norm"], x)
    return tied_embedding.loss(params["embed"], h, labels, run["vocab_size"], num)


def _grads(run, blocks, num, params, tokens, labels):
    """Loss and gradients, the gradients as the state is stored."""
    wide = jax.tree.map(lambda p: p.astype(num.state_store), params)
    return jax.value_and_grad(
        lambda p: loss_fn(run, blocks, num, p, tokens, labels))(wide)


def make_step(run: dict, blocks: dict, num, hyper: dict):
    """One jitted step: (params, state, tokens, labels, t) ->
    (params, state, loss, per-leaf clipped gradient norms).

    ``hyper``: mode (bsp | isp | isp-pod), lr, clip, isp_v, pods, budget,
    block.  For isp-pod the batch rows are split evenly over the pods and
    every state leaf has a leading pod axis.
    """
    mode, lr, clip = hyper["mode"], hyper["lr"], hyper["clip"]
    pods = hyper.get("pods", 1)
    sstore, pstore = num.state_store, num.param_store

    def worker(params, m, v, tokens, labels, t):
        loss, g = _grads(run, blocks, num, params, tokens, labels)
        g = jax.tree.leaves(g)
        scale = update.clip_scale(g, clip) if clip else 1.0
        # the clipped gradient is a tree of the state's dtype
        clipped = lambda x: (x.astype(jnp.float32) * scale).astype(
            sstore).astype(jnp.float32)
        norms = jnp.stack([jnp.sqrt(jnp.sum(jnp.square(clipped(x))))
                           for x in g])
        out = [update.adam(clipped(gl), ml, vl, t, lr, sstore)
               for gl, ml, vl in zip(g, m, v)]
        return loss, norms, [o[0] for o in out], [o[1] for o in out], \
            [o[2] for o in out]

    def step(params, state, tokens, labels, t):
        td = jax.tree.structure(params)
        x = jax.tree.leaves(params)
        m, v, r = state
        # the filter's threshold is read from the optimizer's step counter
        # after its increment: v / sqrt(t + 1) at the t-th step
        v_t = hyper.get("isp_v", 0.0) / jnp.sqrt(t + 1.0)
        if mode != "isp-pod":
            loss, norms, u, m, v = worker(params, m, v, tokens, labels, t)
            u = [ul.astype(sstore) for ul in u]
            if mode == "bsp":
                sent = u
            else:
                acc = [(rl.astype(jnp.float32) + ul.astype(jnp.float32))
                       .astype(sstore) for rl, ul in zip(r, u)]
                split = [update.significance_split(a.astype(jnp.float32),
                                                   xl.astype(jnp.float32), v_t)
                         for a, xl in zip(acc, x)]
                sent = [s for s, _ in split]
                r = [k.astype(sstore) for _, k in split]
            x = [(xl.astype(jnp.float32) + s.astype(jnp.float32)).astype(pstore)
                 for xl, s in zip(x, sent)]
            return td.unflatten(x), (m, v, r), loss, norms

        rows = tokens.shape[0] // pods
        losses, norms_p, u_p, m_p, v_p = [], [], [], [], []
        for k in range(pods):
            sl = slice(k * rows, (k + 1) * rows)
            loss, nrm, u, mk, vk = worker(
                params, [ml[k] for ml in m], [vl[k] for vl in v],
                tokens[sl], labels[sl], t)
            losses.append(loss)
            norms_p.append(nrm)
            u_p.append(u)
            m_p.append(mk)
            v_p.append(vk)
        new_x, new_r = [], []
        for i, xl in enumerate(x):
            xf = xl.astype(jnp.float32)
            combined = jnp.zeros(xl.shape, jnp.float32)
            res = []
            for k in range(pods):
                acc = (r[i][k].astype(jnp.float32)
                       + u_p[k][i].astype(sstore).astype(jnp.float32)
                       ).astype(sstore).astype(jnp.float32)
                sig, kept = update.significance_split(acc, xf, v_t)
                keep = update.block_topk_keep(sig, hyper["block"],
                                              hyper["budget"])
                sent = jnp.where(keep, sig, 0.0)
                res.append((kept + (sig - sent)).astype(sstore))
                combined = combined + sent
            new_r.append(jnp.stack(res))
            new_x.append((xf + combined.astype(pstore).astype(jnp.float32))
                         .astype(pstore))
        m = [jnp.stack([m_p[k][i] for k in range(pods)]) for i in range(len(x))]
        v = [jnp.stack([v_p[k][i] for k in range(pods)]) for i in range(len(x))]
        # the norm of a pod-stacked leaf is over all pods
        norms = jnp.sqrt(jnp.sum(jnp.square(jnp.stack(norms_p)), axis=0))
        return (td.unflatten(new_x), (m, v, new_r), jnp.mean(jnp.stack(losses)),
                norms)

    return jax.jit(step, donate_argnums=(0, 1))


def change_norms(run: dict, blocks: dict, store):
    """jitted (params, key) -> per-leaf norm of (params - the weights drawn
    from key); the weights are drawn again inside, leaf by leaf."""
    flat, _ = _leaves(layout(run, blocks))

    @jax.jit
    def norms(params, key):
        keys = jax.random.split(key, len(flat))
        return jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(
                p.astype(jnp.float32)
                - _draw(l, k).astype(store).astype(jnp.float32))))
            for p, l, k in zip(jax.tree.leaves(params), flat, keys)])

    return norms


def readings(run: dict, blocks: dict, num, hyper: dict, seed: int,
             batches: list) -> dict:
    """Follow ``len(batches)`` steps from the seed's weights.

    Returns ``loss`` (one per step), ``grad`` (per-leaf norm of the first
    step's clipped gradient) and ``change`` (per-leaf norm of the weights'
    change after the last step), as numpy arrays.
    """
    params = init(run, blocks, seed, num.param_store)
    leaves = jax.tree.leaves(params)
    pods = hyper.get("pods", 1) if hyper["mode"] == "isp-pod" else None
    zeros = lambda: [jnp.zeros(((pods,) if pods else ()) + l.shape,
                               num.state_store) for l in leaves]
    state = (zeros(), zeros(), zeros() if hyper["mode"] != "bsp" else [])
    step = make_step(run, blocks, num, hyper)
    losses, grad = [], None
    for t, (tokens, labels) in enumerate(batches, start=1):
        params, state, loss, norms = step(params, state, tokens, labels,
                                          jnp.float32(t))
        losses.append(loss)
        if grad is None:
            grad = norms
    del state
    change = change_norms(run, blocks, num.param_store)(
        params, jax.random.PRNGKey(seed))
    return {"loss": np.asarray(jax.device_get(jnp.stack(losses))),
            "grad": np.asarray(jax.device_get(grad)),
            "change": np.asarray(jax.device_get(change))}
