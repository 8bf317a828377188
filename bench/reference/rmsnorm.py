"""RMSNorm with a scale, eps 1e-6, statistics in float32."""

import jax
import jax.numpy as jnp

EPS = 1e-6


def defs(d: int) -> dict:
    return {"scale": ((d,), "ones")}


def apply(p: dict, x: jax.Array) -> jax.Array:
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + EPS) * p["scale"].astype(jnp.float32)
