"""Model FLOPs of one training step, from the configuration's sizes.

6 N T for the matmuls of the forward (2) and backward (4) passes over all
N parameters (the tied embedding counts once, as the output projection),
plus the attention scores and values: 3 x 4 B H S (S/2) dh per layer under
a causal mask.  Recomputation is not counted.  The same accounting as the
program's ``launch/roofline.model_flops`` for a training shape.
"""

from __future__ import annotations


def train_step_flops(run: dict, params: int, rows: int, seq: int) -> float:
    h, dh = run["num_attention_heads"], run["head_dim"]
    attn = run["num_hidden_layers"] * 3 * 4.0 * rows * h * seq * (seq / 2) * dh
    return 6.0 * params * rows * seq + attn
