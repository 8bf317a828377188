"""mellum2-12b-a2.5b [moe]: 28L in a period of [sliding, sliding, sliding,
full], window 1024, d_model=2304, 32H (GQA kv=4, head_dim 128), untied
vocab=98304. Every FF is sparse: 64 SwiGLU experts of width 896, top-8 with
renormalised gates, no shared expert. RoPE base 5e5 on every layer (the
published full layers add YaRN, factor 16, which the program does not
build). [hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]

``EP4`` is one chip's share of a v5e host whose four chips share each
expert layer: 16 of the 64 experts (0 .. 15), the router at its full
width.  A capacity factor of 64 / 8 states that the layer is dropless
(``models.moe.dropless``)."""

import dataclasses
from types import SimpleNamespace

from repro.models.config import (
    ArchConfig, BlockSpec, FF, Mixer, MoEConfig, pattern_groups,
)

_SLIDING = BlockSpec(Mixer.LOCAL_ATTN, FF.MOE, window=1024,
                     rope_base=500_000.0)
_FULL = BlockSpec(Mixer.GLOBAL_ATTN, FF.MOE, rope_base=500_000.0)

CONFIG = ArchConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    d_ff=896,  # the experts' width
    vocab_size=98_304,
    head_dim=128,
    groups=pattern_groups((_SLIDING,) * 3 + (_FULL,), 28),
    moe=MoEConfig(n_experts=64, top_k=8, capacity_factor=8.0),
    tie_embeddings=False,
    max_seq_len=131_072,
    sub_quadratic=False,  # a full layer in every four
)

_SM_PATTERN = (
    BlockSpec(Mixer.LOCAL_ATTN, FF.MOE, window=16, rope_base=500_000.0),
) * 3 + (BlockSpec(Mixer.GLOBAL_ATTN, FF.MOE, rope_base=500_000.0),)

SMOKE = ArchConfig(
    name="mellum2-smoke",
    family="moe",
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    d_ff=32,
    vocab_size=256,
    head_dim=32,
    groups=pattern_groups(_SM_PATTERN, 4),
    moe=MoEConfig(n_experts=16, top_k=4, capacity_factor=4.0),
    tie_embeddings=False,
    max_seq_len=128,
    sub_quadratic=False,
)


def _held(cfg: ArchConfig, name: str, held: int) -> ArchConfig:
    return dataclasses.replace(
        cfg, name=name, moe=dataclasses.replace(cfg.moe, n_held=held))


EP4 = SimpleNamespace(
    CONFIG=_held(CONFIG, "mellum2-12b-a2.5b.ep4", 16),
    SMOKE=_held(SMOKE, "mellum2-smoke.ep4", 4),
)
