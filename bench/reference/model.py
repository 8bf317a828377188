"""The reference decoder, its initial weights from a seed, and its steps.

A configuration file (``bench/configs/<name>.json``) gives the sizes under
``run`` and the block kinds under ``blocks``; this module builds the model
from those alone.  The layers repeat a period of positions: the smallest
period of the file's ``layer_types`` (one position where it gives none).
The parameter tree has the layout and leaf order of the published model
as the program stores it (one stacked entry ``b<i>`` per position of the
period, with a leading axis over its repeats; sorted names), so the
weights drawn from a seed are the same draw: the seed's key is split into
one key per leaf in that order, and a weight matrix is a standard normal
scaled by 1/sqrt(its second-to-last dimension).  A leaf is stored in the
configuration's parameter dtype unless its kind states its own.

``readings`` follows the first steps of a training run and returns what the
benchmark compares: each step's loss, each leaf's clipped first gradient
norm, and each leaf's change after the last step.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import update

PyTree = Any
SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Leaf:
    shape: tuple
    init: str  # normal | zeros | ones
    dtype: Optional[str] = None  # None: the configuration's parameter dtype
    expert: bool = False  # one routed expert's weights, stacked by expert


def _kind(name: str):
    return importlib.import_module(f"bench.reference.{name}")


def embedding(run: dict):
    return _kind("tied_embedding" if run.get("tie_word_embeddings", True)
                 else "untied_embedding")


def layer_kinds(run: dict) -> list[str]:
    """Each layer's attention kind: the file's ``layer_types``; without
    them, every layer slides where the file gives a ``sliding_window``,
    else none does."""
    n = run["num_hidden_layers"]
    if "layer_types" in run:
        kinds = list(run["layer_types"])
        if len(kinds) != n or not set(kinds) <= {SLIDING, FULL}:
            raise ValueError(f"layer_types: {n} of {SLIDING!r} or {FULL!r} "
                             f"expected, got {kinds}")
        return kinds
    return [SLIDING if run.get("sliding_window") else FULL] * n


def period(run: dict) -> int:
    """The smallest number of layers whose kinds repeat through the stack."""
    kinds = layer_kinds(run)
    n = len(kinds)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))


def rope_theta(run: dict, kind: str) -> float:
    """The RoPE base of a layer kind: ``rope_parameters`` (one entry, or one
    per layer kind) where the file gives them, else ``rope_theta``."""
    params = run.get("rope_parameters")
    if params is None:
        return run["rope_theta"]
    entry = params.get(kind, params)
    if entry.get("rope_type", "default") != "default":
        raise ValueError(f"rope_parameters: {kind} has rope_type "
                         f"{entry['rope_type']!r}; only 'default' is built")
    return entry["rope_theta"]


def positions(run: dict) -> list[dict]:
    """Per position of the period: the mixer's window and RoPE base."""
    kinds = layer_kinds(run)[: period(run)]
    return [{"window": run["sliding_window"] if k == SLIDING else None,
             "rope_base": rope_theta(run, k)} for k in kinds]


def _leaf(spec) -> Leaf:
    return spec if isinstance(spec, Leaf) else Leaf(*spec)


def layout(run: dict, blocks: dict) -> PyTree:
    d = run["hidden_size"]
    reps = run["num_hidden_layers"] // period(run)
    norm = _kind(blocks["norm"])
    block = {
        "ff": _kind(blocks["ff"]).defs(run),
        "mixer": _kind(blocks["mixer"]).defs(run),
        "norm1": norm.defs(d),
        "norm2": norm.defs(d),
    }
    stack = lambda l: dataclasses.replace(l, shape=(reps,) + l.shape)
    stacked = {part: {k: stack(_leaf(s)) for k, s in leaves.items()}
               for part, leaves in block.items()}
    flat = lambda t: {k: _leaf(s) for k, s in t.items()}
    return {"embed": flat(embedding(run).defs(run["vocab_size"], d)),
            "final_norm": flat(norm.defs(d)),
            "groups": [{f"b{i}": stacked for i in range(period(run))}]}


def _leaves(tree):
    return jax.tree.flatten(tree, is_leaf=lambda x: isinstance(x, Leaf))


def leaf_stores(run: dict, blocks: dict, store) -> list:
    """Each leaf's storage dtype: its own, or ``store``."""
    return [jnp.dtype(l.dtype) if l.dtype else store
            for l in _leaves(layout(run, blocks))[0]]


def _draw(leaf: Leaf, key) -> jax.Array:
    if leaf.init == "zeros":
        return jnp.zeros(leaf.shape, jnp.float32)
    if leaf.init == "ones":
        return jnp.ones(leaf.shape, jnp.float32)
    fan_in = leaf.shape[-2] if len(leaf.shape) >= 2 else leaf.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.maximum(fan_in, 1)).astype(jnp.float32)
    return scale * jax.random.normal(key, leaf.shape, jnp.float32)


def init(run: dict, blocks: dict, seed: int, store) -> PyTree:
    """The weights drawn from ``seed``, stored as ``leaf_stores`` says, in
    one call."""
    flat, tree = _leaves(layout(run, blocks))
    stores = leaf_stores(run, blocks, store)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(flat))
        return tree.unflatten([_draw(l, k).astype(s)
                               for l, k, s in zip(flat, keys, stores)])

    return make(jax.random.PRNGKey(seed))


def leaf_names(run: dict, blocks: dict) -> list[str]:
    paths, _ = jax.tree_util.tree_flatten_with_path(
        layout(run, blocks), is_leaf=lambda x: isinstance(x, Leaf))
    return [jax.tree_util.keystr(p) for p, _ in paths]


def loss_fn(run: dict, blocks: dict, num, params: PyTree, tokens, labels):
    """Mean cross-entropy plus ``router_aux_loss_coef`` times the sum of the
    layers' auxiliary terms."""
    norm = _kind(blocks["norm"])
    ff = _kind(blocks["ff"])
    mixer = _kind(blocks["mixer"])
    emb = embedding(run)

    def layer(p, x, *, where):
        x = x + mixer.apply(p["mixer"], norm.apply(p["norm1"], x), num, run,
                            **where)
        y, aux = ff.apply(p["ff"], norm.apply(p["norm2"], x), num, run)
        return x + y, aux

    layers = [jax.checkpoint(functools.partial(layer, where=w))
              for w in positions(run)]
    x = emb.embed(params["embed"], tokens, num)
    aux = 0.0
    group = params["groups"][0]
    for r in range(run["num_hidden_layers"] // len(layers)):
        for i, apply in enumerate(layers):
            x, a = apply(jax.tree.map(lambda t: t[r], group[f"b{i}"]), x)
            aux = aux + a
    h = norm.apply(params["final_norm"], x)
    xent = emb.loss(params["embed"], h, labels, run["vocab_size"], num)
    coef = run.get("router_aux_loss_coef", 0.0)
    return xent + coef * aux if coef else xent


def _grads(run, blocks, num, params, tokens, labels):
    """Loss and gradients, the gradients as the state is stored."""
    stores = leaf_stores(run, blocks, num.state_store)
    td = jax.tree.structure(params)
    wide = td.unflatten([p.astype(s) for p, s in
                         zip(jax.tree.leaves(params), stores)])
    return jax.value_and_grad(
        lambda p: loss_fn(run, blocks, num, p, tokens, labels))(wide)


def make_step(run: dict, blocks: dict, num, hyper: dict):
    """One jitted step: (params, state, tokens, labels, t) ->
    (params, state, loss, per-leaf clipped gradient norms).

    ``hyper``: mode (bsp | isp | isp-pod), lr, clip, isp_v, pods, budget,
    block.  For isp-pod the batch rows are split evenly over the pods and
    every state leaf has a leading pod axis.  Each leaf's state is stored
    as the leaf's own dtype where it states one, else in the numerics'.
    """
    mode, lr, clip = hyper["mode"], hyper["lr"], hyper["clip"]
    pods = hyper.get("pods", 1)
    sstores = leaf_stores(run, blocks, num.state_store)
    pstores = leaf_stores(run, blocks, num.param_store)

    def worker(params, m, v, tokens, labels, t):
        loss, g = _grads(run, blocks, num, params, tokens, labels)
        g = jax.tree.leaves(g)
        scale = update.clip_scale(g, clip) if clip else 1.0
        # the clipped gradient is a tree of the state's dtype
        clipped = lambda x, s: (x.astype(jnp.float32) * scale).astype(
            s).astype(jnp.float32)
        norms = jnp.stack([jnp.sqrt(jnp.sum(jnp.square(clipped(x, s))))
                           for x, s in zip(g, sstores)])
        out = [update.adam(clipped(gl, s), ml, vl, t, lr, s)
               for gl, ml, vl, s in zip(g, m, v, sstores)]
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
            u = [ul.astype(s) for ul, s in zip(u, sstores)]
            if mode == "bsp":
                sent = u
            else:
                acc = [(rl.astype(jnp.float32) + ul.astype(jnp.float32))
                       .astype(s) for rl, ul, s in zip(r, u, sstores)]
                split = [update.significance_split(a.astype(jnp.float32),
                                                   xl.astype(jnp.float32), v_t)
                         for a, xl in zip(acc, x)]
                sent = [s for s, _ in split]
                r = [k.astype(s) for (_, k), s in zip(split, sstores)]
            x = [(xl.astype(jnp.float32) + s.astype(jnp.float32)).astype(p)
                 for xl, s, p in zip(x, sent, pstores)]
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
            sstore, pstore = sstores[i], pstores[i]
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
    stores = leaf_stores(run, blocks, store)

    @jax.jit
    def norms(params, key):
        keys = jax.random.split(key, len(flat))
        return jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(
                p.astype(jnp.float32)
                - _draw(l, k).astype(s).astype(jnp.float32))))
            for p, l, k, s in zip(jax.tree.leaves(params), flat, keys,
                                  stores)])

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
    sstores = leaf_stores(run, blocks, num.state_store)
    pods = hyper.get("pods", 1) if hyper["mode"] == "isp-pod" else None
    zeros = lambda: [jnp.zeros(((pods,) if pods else ()) + l.shape, s)
                     for l, s in zip(leaves, sstores)]
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
