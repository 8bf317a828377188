"""Tied token embedding and the cross-entropy over its transpose.

The table has the vocabulary rounded up to a multiple of 256 rows; the
rows past the vocabulary take no part in the softmax.  Inputs are scaled
by sqrt(d).  The loss is the mean over all positions, computed over
chunks of rows that are recomputed in the backward pass.
"""

import jax
import jax.numpy as jnp

LOGIT_BYTES = 256 << 20  # logits of one chunk of rows, float32


def padded(vocab: int) -> int:
    return (vocab + 255) // 256 * 256


def defs(vocab: int, d: int) -> dict:
    return {"tok": ((padded(vocab), d), "normal")}


def embed(p: dict, tokens: jax.Array, num) -> jax.Array:
    tab = num.rnd(p["tok"])
    return tab[tokens] * jnp.sqrt(jnp.float32(tab.shape[1]))


def loss(p: dict, h: jax.Array, labels: jax.Array, vocab: int, num) -> jax.Array:
    """Mean cross-entropy of hidden states h (B, S, d) against labels."""
    w = p["tok"]
    vp, d = w.shape
    rows = h.reshape(-1, d)
    ys = labels.reshape(-1)
    n = rows.shape[0]
    c = max(1, min(n, LOGIT_BYTES // (4 * vp)))
    while n % c:
        c -= 1
    bias = jnp.where(jnp.arange(vp) < vocab, 0.0, -jnp.inf)

    @jax.checkpoint
    def chunk(args):
        h_c, y_c = args
        logits = num.mm(h_c, w.T) + bias
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, y_c[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - lab)

    parts = jax.lax.map(chunk, (rows.reshape(n // c, c, d),
                                ys.reshape(n // c, c)))
    return jnp.sum(parts) / n
