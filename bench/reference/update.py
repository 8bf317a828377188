"""The update side of a step, leaf by leaf, in float32.

clip: scale every gradient by min(1, max_norm / global norm).
adam: m = b1 m + (1-b1) g, v = b2 v + (1-b2) g^2 (stored), then
      u = -lr (m / (1 - b1^t)) / (sqrt(v / (1 - b2^t)) + eps).
significance split (MLLess ISP): acc = r + u; the entries with
      |acc| > v_t max(|x|, floor) are sent, the rest stay in r.
block top-k: per worker, of each run of ``block`` entries of the flattened
      leaf, keep the round(block * budget) (at least 1) of largest magnitude;
      the significant entries not kept go back to the residual.  Ties go to
      the lower index.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

B1, B2, EPS = 0.9, 0.999, 1e-8
FLOOR = 1e-8


def global_norm(leaves) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves))


def clip_scale(leaves, max_norm: float) -> jax.Array:
    return jnp.minimum(1.0, max_norm / jnp.maximum(global_norm(leaves), 1e-12))


def adam(g, m, v, t, lr, store):
    """One Adam step of one leaf; returns (u, m, v), m and v as stored."""
    m = (B1 * m.astype(jnp.float32) + (1 - B1) * g).astype(store)
    v = (B2 * v.astype(jnp.float32) + (1 - B2) * jnp.square(g)).astype(store)
    mhat = m.astype(jnp.float32) / (1.0 - B1 ** t)
    vhat = v.astype(jnp.float32) / (1.0 - B2 ** t)
    u = -lr * mhat / (jnp.sqrt(vhat) + EPS)
    return u, m, v


def significance_split(acc, x, v_t):
    """(sent, kept) with sent + kept == acc."""
    mask = jnp.abs(acc) > v_t * jnp.maximum(jnp.abs(x), FLOOR)
    return jnp.where(mask, acc, 0.0), jnp.where(mask, 0.0, acc)


def block_topk_keep(sig, block: int, budget: float) -> jax.Array:
    """Keep-mask of one worker's leaf (any shape)."""
    n = sig.size
    blk = min(block, max(n, 1))
    k = max(1, min(blk, int(round(blk * budget))))
    flat = sig.reshape(-1)
    pad = (-n) % blk
    flat = jnp.pad(flat, (0, pad))
    rows = flat.reshape(-1, blk)
    _, idx = jax.lax.top_k(jnp.abs(rows), k)
    keep = jnp.zeros(rows.shape, bool)
    keep = keep.at[jnp.arange(rows.shape[0])[:, None], idx].set(True)
    return keep.reshape(-1)[:n].reshape(sig.shape)
