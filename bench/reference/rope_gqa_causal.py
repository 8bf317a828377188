"""Causal self-attention with grouped KV heads and rotary positions.

Projections are flat: W_q (d, H*dh), W_k and W_v (d, K*dh), W_o (H*dh, d).
Query head h reads KV head h // (H / K).  RoPE rotates the two halves of
each head (first half with second half) at frequencies base^(-i / (dh/2)).
Queries are taken in chunks, each recomputed in the backward pass, so the
(B, H, chunk, S) scores of one chunk are all that is held at a time.
"""

import jax
import jax.numpy as jnp

SCORE_BYTES = 512 << 20  # scores of one query chunk, float32


def defs(d: int, n_heads: int, n_kv: int, head_dim: int) -> dict:
    return {"wk": ((d, n_kv * head_dim), "normal"),
            "wo": ((n_heads * head_dim, d), "normal"),
            "wq": ((d, n_heads * head_dim), "normal"),
            "wv": ((d, n_kv * head_dim), "normal")}


def rope(x: jax.Array, base: float) -> jax.Array:
    """x (B, S, heads, dh) at positions 0..S-1."""
    s, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs  # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _query_chunk(b: int, s: int, heads: int) -> int:
    c = max(1, min(s, SCORE_BYTES // (4 * b * heads * s)))
    while s % c:
        c -= 1
    return c


def apply(p: dict, x: jax.Array, num, *, n_heads: int, n_kv: int,
          head_dim: int, rope_base: float) -> jax.Array:
    b, s, _ = x.shape
    q = num.mm(x, p["wq"]).reshape(b, s, n_heads, head_dim)
    k = num.mm(x, p["wk"]).reshape(b, s, n_kv, head_dim)
    v = num.mm(x, p["wv"]).reshape(b, s, n_kv, head_dim)
    q, k = rope(q, rope_base), rope(k, rope_base)
    group = n_heads // n_kv
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    qc = _query_chunk(b, s, n_heads)
    n = s // qc
    qs = q.reshape(b, n, qc, n_heads, head_dim).swapaxes(0, 1)
    scale = 1.0 / jnp.sqrt(jnp.float32(head_dim))

    @jax.checkpoint
    def chunk(args):
        i, q_c = args
        logits = num.einsum("bqhd,bkhd->bhqk", q_c, k) * scale
        q_pos = i * qc + jnp.arange(qc)
        allow = jnp.arange(s)[None, :] <= q_pos[:, None]
        logits = jnp.where(allow[None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        return num.einsum("bhqk,bkhd->bqhd", probs, v)

    out = jax.lax.map(chunk, (jnp.arange(n), qs))  # (n, B, qc, H, dh)
    o = out.swapaxes(0, 1).reshape(b, s, n_heads * head_dim)
    return num.mm(o, p["wo"])
