"""Untied token embedding: an input table and a separate output matrix.

``tok`` (the vocabulary rounded up to a multiple of 256 rows, d) embeds the
tokens, scaled by sqrt(d) as the tied embedding does; ``unembed`` (d, the
same padded vocabulary) gives the logits, with the padded columns out of
the softmax.  Both are drawn as every normal leaf is: scaled by 1/sqrt of
the second-to-last dimension.
"""

import jax

from bench.reference import tied_embedding

embed = tied_embedding.embed


def defs(vocab: int, d: int) -> dict:
    vp = tied_embedding.padded(vocab)
    return {"tok": ((vp, d), "normal"), "unembed": ((d, vp), "normal")}


def loss(p: dict, h: jax.Array, labels: jax.Array, vocab: int,
         num) -> jax.Array:
    """Mean cross-entropy of hidden states h (B, S, d) against labels."""
    return tied_embedding.xent(p["unembed"], h, labels, vocab, num)
