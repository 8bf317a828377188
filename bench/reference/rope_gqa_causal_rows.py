"""``rope_gqa_causal``, one batch row at a time: the same attention in a
fraction of its memory, for long sequences.

The function is ``rope_gqa_causal.apply``'s: the same flat projections, the
same RoPE, and query head h reading KV head h // (H / K), through a grouped
product instead of repeated KV heads.  Each batch row, projections
included, is computed on its own and recomputed in the backward pass, and
its queries are taken in chunks whose (H, chunk, S) scores hold at most
``SCORE_BYTES``, each recomputed in turn.  So the backward pass holds one
row's activations and one chunk's scores at a time.
"""

from typing import Optional

import jax
import jax.numpy as jnp

from bench.reference import rope_gqa_causal

SCORE_BYTES = 128 << 20  # scores of one query chunk of one row, float32

defs = rope_gqa_causal.defs


def apply(p: dict, x: jax.Array, num, run: dict, *, rope_base: float,
          window: Optional[int] = None) -> jax.Array:
    n_heads, n_kv = run["num_attention_heads"], run["num_key_value_heads"]
    head_dim = run["head_dim"]
    group = n_heads // n_kv
    s = x.shape[1]
    qc = max(1, min(s, SCORE_BYTES // (4 * n_heads * s)))
    while s % qc:
        qc -= 1
    scale = 1.0 / jnp.sqrt(jnp.float32(head_dim))

    def rotated(xr, w, n):
        return rope_gqa_causal.rope(num.mm(xr, w).reshape(1, s, n, head_dim),
                                    rope_base)[0]

    @jax.checkpoint
    def row(xr):
        q = rotated(xr, p["wq"], n_heads).reshape(s, n_kv, group, head_dim)
        k = rotated(xr, p["wk"], n_kv)
        v = num.mm(xr, p["wv"]).reshape(s, n_kv, head_dim)

        @jax.checkpoint
        def chunk(args):
            i, q_c = args
            logits = num.einsum("qkgd,skd->kgqs", q_c, k) * scale
            q_pos = i * qc + jnp.arange(qc)
            allow = jnp.arange(s)[None, :] <= q_pos[:, None]
            if window is not None:
                allow = allow & (q_pos[:, None] - jnp.arange(s)[None, :]
                                 < window)
            logits = jnp.where(allow[None, None], logits, -jnp.inf)
            probs = jax.nn.softmax(logits, axis=-1)
            return num.einsum("kgqs,skd->qkgd", probs, v)

        out = jax.lax.map(chunk, (jnp.arange(s // qc),
                                  q.reshape(s // qc, qc, n_kv, group,
                                            head_dim)))
        return num.mm(out.reshape(s, n_heads * head_dim), p["wo"])

    return jax.lax.map(row, x)
