"""Model FLOPs of one training step, from the configuration's sizes.

6 N T for the matmuls of the forward (2) and backward (4) passes, with N the
parameters a token is computed with: every leaf but the routed experts'
(the tied embedding counts once, as the output projection), plus the
experts' leaves times num_experts_per_tok / num_experts_total, the
assignments a token makes to the experts held here.  Then the attention
scores and values: 3 x 4 B H P dh per layer, where P, the attended pairs
of a row, is S^2 / 2 under a causal mask and w S - w^2 / 2 under a sliding
window w < S.  Recomputation is not counted.  For a stack of full layers
with no experts, the same accounting as the program's
``launch/roofline.model_flops`` for a training shape.
"""

from __future__ import annotations

import math

from bench.reference import model


def active_params(run: dict, leaves: list) -> float:
    """N of 6 N T from the layout's leaves (``model.Leaf``)."""
    dense = sum(math.prod(l.shape) for l in leaves if not l.expert)
    experts = sum(math.prod(l.shape) for l in leaves if l.expert)
    if not experts:
        return dense
    total = run.get("num_experts_total", run["num_experts"])
    return dense + experts * run["num_experts_per_tok"] / total


def attended_pairs(seq: int, window=None) -> float:
    """Query-key pairs of one row and head: causal, within the window."""
    if window is None or window >= seq:
        return seq * (seq / 2)
    return window * seq - window * window / 2


def train_step_flops(run: dict, n_active: float, rows: int, seq: int) -> float:
    h, dh = run["num_attention_heads"], run["head_dim"]
    kinds = model.layer_kinds(run)
    attn = 0.0
    for kind, window in ((model.FULL, None),
                         (model.SLIDING, run.get("sliding_window"))):
        if kind in kinds:
            attn += (kinds.count(kind) * 3 * 4.0 * rows * h
                     * attended_pairs(seq, window) * dh)
    return 6.0 * n_active * rows * seq + attn
