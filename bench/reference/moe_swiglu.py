"""Mixture-of-experts SwiGLU feed-forward, dropless.

Routing: the router (d, E) is stored and applied in float32 at the highest
precision; a softmax over all E experts gives each token's probabilities;
its ``num_experts_per_tok`` (k) largest are kept and, with
``norm_topk_prob`` (the default), divided by their sum.  Every assignment
is computed: each held expert runs the SwiGLU of ``swiglu`` at width
``moe_intermediate_size`` over every token, and its output is weighted by
the token's gate for it (0 where the token did not choose it).

A layer may hold fewer experts than it routes over: ``num_experts`` held,
of ``num_experts_total`` (the published count; default all).  It holds
experts ``first`` .. ``first + num_experts - 1`` and gives their part of
the output alone; the router keeps its full width.

The auxiliary term is Switch's load balance: E * sum_e (f_e / k) P_e, with
f_e the mean over tokens of how many of a token's k choices are e, and P_e
the mean probability of e, both over every token and all E experts.
"""

import jax
import jax.numpy as jnp

from bench.reference.model import Leaf
from bench.reference.numerics import HIGHEST


def defs(run: dict) -> dict:
    d, f = run["hidden_size"], run["moe_intermediate_size"]
    held = run["num_experts"]
    total = run.get("num_experts_total", held)
    return {"router": Leaf((d, total), "normal", "float32"),
            "w_down": Leaf((held, f, d), "normal", expert=True),
            "w_gate": Leaf((held, d, f), "normal", expert=True),
            "w_up": Leaf((held, d, f), "normal", expert=True)}


def route(router: jax.Array, rows: jax.Array, run: dict):
    """(gates (N, E): each token's weight for each expert, aux term)."""
    e, k = router.shape[-1], run["num_experts_per_tok"]
    logits = jnp.matmul(rows.astype(jnp.float32), router.astype(jnp.float32),
                        precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top, ids = jax.lax.top_k(probs, k)
    if run.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(ids, e, dtype=jnp.float32)  # (N, k, E)
    gates = jnp.sum(chosen * top[..., None], axis=1)
    density = jnp.mean(jnp.sum(chosen, axis=1), axis=0)
    aux = e * jnp.sum(density / k * jnp.mean(probs, axis=0))
    return gates, aux


def apply(p: dict, x: jax.Array, num, run: dict, first: int = 0):
    """(the held experts' part of the output, aux term)."""
    rows = x.reshape(-1, x.shape[-1])
    gates, aux = route(p["router"], rows, run)
    held = p["w_gate"].shape[0]
    gates = gates[:, first:first + held]

    @jax.checkpoint
    def expert(out, w):
        w_gate, w_up, w_down, gate = w
        g = num.mm(rows, w_gate)
        act = g / (1.0 + jnp.exp(-g))
        return out + gate[:, None] * num.mm(act * num.mm(rows, w_up),
                                            w_down), None

    out, _ = jax.lax.scan(expert, jnp.zeros(rows.shape, jnp.float32),
                          (p["w_gate"], p["w_up"], p["w_down"], gates.T))
    return out.reshape(x.shape), aux
