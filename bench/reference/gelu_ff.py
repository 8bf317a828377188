"""Two-layer GELU feed-forward with biases (tanh-approximated GELU)."""

import jax
import jax.numpy as jnp


def defs(run: dict) -> dict:
    d, f = run["hidden_size"], run["intermediate_size"]
    return {"b_down": ((d,), "zeros"), "b_up": ((f,), "zeros"),
            "w_down": ((f, d), "normal"), "w_up": ((d, f), "normal")}


def _gelu(x: jax.Array) -> jax.Array:
    c = jnp.sqrt(2.0 / jnp.pi).astype(jnp.float32)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def apply(p: dict, x: jax.Array, num, run: dict) -> tuple[jax.Array, float]:
    """(output, auxiliary loss term: none)."""
    h = _gelu(num.mm(x, p["w_up"]) + p["b_up"].astype(jnp.float32))
    return num.mm(h, p["w_down"]) + p["b_down"].astype(jnp.float32), 0.0
