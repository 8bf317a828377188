"""LayerNorm with scale and bias, eps 1e-6, statistics in float32."""

import jax
import jax.numpy as jnp

EPS = 1e-6


def defs(d: int) -> dict:
    return {"bias": ((d,), "zeros"), "scale": ((d,), "ones")}


def apply(p: dict, x: jax.Array) -> jax.Array:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + EPS)
    return y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
