"""Architecture configuration — one dataclass drives the whole LM stack.

A model is a stack of *scan groups*; each group is a repeated *superblock*;
a superblock is an ordered tuple of block specs (attention / MoE-FF / RG-LRU /
mLSTM / sLSTM ...). Heterogeneous layer patterns (gemma3's 5 local : 1 global,
recurrentgemma's 2 recurrent : 1 attention, xLSTM's 7 mLSTM : 1 sLSTM) are
expressed as superblocks so the whole depth still lowers as ONE ``lax.scan``
per group — HLO size stays O(pattern), not O(depth), which is what keeps
512-device compiles tractable (DESIGN.md §5).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional


class Mixer(enum.Enum):
    """Sequence-mixing block kinds."""

    GLOBAL_ATTN = "global_attn"  # full (causal) attention
    LOCAL_ATTN = "local_attn"  # sliding-window attention
    CROSS_ATTN = "cross_attn"  # encoder-decoder cross attention
    RGLRU = "rglru"  # Griffin-style gated linear recurrence
    MLSTM = "mlstm"  # xLSTM matrix-memory block
    SLSTM = "slstm"  # xLSTM scalar-memory block (sequential)


class FF(enum.Enum):
    """Feed-forward kinds (NONE for xLSTM blocks with internal projections)."""

    SWIGLU = "swiglu"
    GEGLU = "geglu"
    GELU = "gelu"  # plain 2-layer MLP
    MOE = "moe"
    NONE = "none"


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """One residual block: pre-norm mixer + pre-norm FF."""

    mixer: Mixer
    ff: FF
    window: Optional[int] = None  # sliding-window size (LOCAL_ATTN)
    rope_base: Optional[float] = 10_000.0  # None = no RoPE (whisper)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int  # the router's width
    top_k: int = 2
    capacity_factor: float = 1.25
    # experts held here, 0 .. n_held - 1 (None: all); the held path only
    n_held: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Auxiliary encoder (whisper audio / paligemma vision-stub)."""

    n_layers: int
    ctx_len: int  # 1500 audio frames / 256 image patches
    d_model: Optional[int] = None  # defaults to decoder d_model
    precomputed: bool = True  # frontend is a stub: embeddings arrive as input


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | hybrid | ssm | audio | vlm
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    # (superblock, repeats) groups; sum(len(sb) * reps) == total layers
    groups: tuple[tuple[tuple[BlockSpec, ...], int], ...]
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    moe: Optional[MoEConfig] = None
    encoder: Optional[EncoderConfig] = None  # enc-dec / VLM prefix tower
    prefix_lm: bool = False  # paligemma: bidirectional prefix attention
    tie_embeddings: bool = True
    max_seq_len: int = 131_072
    sub_quadratic: bool = False  # long_500k eligibility (DESIGN.md §4)
    # dtypes
    param_dtype: str = "bfloat16"
    activation_dtype: str = "bfloat16"
    # slstm/mlstm internal expansion
    lstm_proj_factor: float = 2.0

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to a multiple of 256 so the embedding table's
        vocab dim is shardable over any mesh axis (16/32/...). Padded logit
        columns are masked out of the softmax (layers.chunked_softmax_xent);
        padded rows are dead weights. Standard MaxText-style practice."""
        return ((self.vocab_size + 255) // 256) * 256

    @property
    def n_layers(self) -> int:
        return sum(len(sb) * reps for sb, reps in self.groups)

    def validate(self) -> None:
        assert self.n_heads % self.n_kv_heads == 0, (
            f"{self.name}: heads {self.n_heads} % kv {self.n_kv_heads} != 0"
        )
        for sb, reps in self.groups:
            assert reps >= 1 and len(sb) >= 1
            for b in sb:
                if b.ff is FF.MOE:
                    assert self.moe is not None, f"{self.name}: MOE ff without moe cfg"
                if b.mixer is Mixer.LOCAL_ATTN:
                    assert b.window, f"{self.name}: local attn without window"


def uniform_groups(spec: BlockSpec, n_layers: int) -> tuple:
    """Homogeneous stack: one group of n_layers single-block superblocks."""
    return (((spec,), n_layers),)


def pattern_groups(pattern: tuple[BlockSpec, ...], n_layers: int) -> tuple:
    """Repeat ``pattern`` as a superblock; remainder becomes a second group."""
    plen = len(pattern)
    reps, rem = divmod(n_layers, plen)
    groups = []
    if reps:
        groups.append((pattern, reps))
    if rem:
        groups.append((pattern[:rem], 1))
    return tuple(groups)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str  # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def shape_applicable(arch: ArchConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """The DESIGN.md §4 applicability matrix."""
    if shape.name == "long_500k" and not arch.sub_quadratic:
        return False, "skipped(full-attention)"
    return True, "ok"
