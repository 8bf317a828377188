"""SwiGLU feed-forward: (silu(x W_gate) * x W_up) W_down, no biases."""

import jax
import jax.numpy as jnp


def defs(d: int, f: int) -> dict:
    return {"w_down": ((f, d), "normal"), "w_gate": ((d, f), "normal"),
            "w_up": ((d, f), "normal")}


def apply(p: dict, x: jax.Array, num) -> jax.Array:
    g = num.mm(x, p["w_gate"])
    act = g / (1.0 + jnp.exp(-g))
    return num.mm(act * num.mm(x, p["w_up"]), p["w_down"])
