"""SwiGLU feed-forward: (silu(x W_gate) * x W_up) W_down, no biases."""

import jax
import jax.numpy as jnp


def defs(run: dict) -> dict:
    d, f = run["hidden_size"], run["intermediate_size"]
    return {"w_down": ((f, d), "normal"), "w_gate": ((d, f), "normal"),
            "w_up": ((d, f), "normal")}


def apply(p: dict, x: jax.Array, num, run: dict) -> tuple[jax.Array, float]:
    """(output, auxiliary loss term: none)."""
    g = num.mm(x, p["w_gate"])
    act = g / (1.0 + jnp.exp(-g))
    return num.mm(act * num.mm(x, p["w_up"]), p["w_down"]), 0.0
