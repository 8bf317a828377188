"""Attention: GQA + RoPE + causal / sliding-window / prefix-LM / cross.

Execution paths (cores), chosen by ``pick_core`` from the backend, the
sequence length, the mask kind and the partitioning:

* ``flash``   — JAX's Pallas splash-attention kernel, forward and a
  recomputing backward: the forward keeps only the output and each row's
  log-sum-exp, the backward rebuilds each block's probabilities in VMEM, and
  blocks the causal or window mask empties are skipped.  Train/prefill of
  causal self-attention at long sequence on one TPU.
* ``dense``   — single einsum + masked softmax. Decode (q_len == 1) and short
  sequences. Memory O(Sq*Skv).
* ``chunked`` — outer ``lax.scan`` over Q chunks (rematerialized), inner scan
  over KV chunks with online-softmax accumulation: the XLA-level flash
  attention. Memory O(chunk^2). Train/prefill at long seq off the TPU, or
  where the flash core does not apply.
  NOTE: the inner scan visits all KV chunks and masks — causal upper-triangle
  tiles are wasted flops in this XLA fallback.
* ``kv_chunked`` — the same online softmax with q kept whole, for a
  sequence-sharded q.
* ``banded``  — sliding-window layers slice one static-width KV band per Q
  chunk (``dynamic_slice``), so SWA flops are O(Sq * window), not O(Sq^2).

Softmax statistics are fp32 regardless of activation dtype (the flash core
takes its matmul operands in the activation dtype, accumulating in fp32).
Each core runs inside the named scope ``attn_core`` (``SCOPES``), so a
profile can tell the attention itself from the projections around it.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash
from jax.sharding import PartitionSpec as P

from repro.models.config import ArchConfig, BlockSpec, Mixer
from repro.models.layers import adt, pdt, rope
from repro.models.params import ParamDef

PyTree = Any

NEG_INF = -1e30
SCOPES = ("attn_core",)
_DEFAULT_CHUNK = 1024
_DENSE_MAX_SEQ = 2048  # dense path threshold
_LANES = 128  # the flash kernel's head dim and blocks are whole lane tiles


def _divisor_chunk(s: int, chunk: int) -> int:
    """Largest divisor of ``s`` that is <= ``chunk`` (paligemma's
    vision-prefixed sequences are not powers of two)."""
    c = min(chunk, s)
    while s % c:
        c -= 1
    return c


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Activation-sharding annotations (mesh axis names), per shape cell."""

    batch: Any = ("pod", "data")
    heads: Any = "model"
    kv_seq: Any = None  # set to 'data' for long-context decode (cache SP)
    seq: Any = None  # sequence-parallel axis for the residual stream
    moe_groups: int = 1  # group-local MoE dispatch (== # of batch shards)
    moe_group_ax: Any = None  # mesh axes of the MoE group dim
    moe_token_ax: Any = None  # mesh axis of tokens within a group
    moe_ep_ax: Any = None  # expert-parallel axis (decode only: tiny buffers)
    moe_f_ax: Any = None  # d_ff compute sharding of expert weights
    moe_a2a: bool = False  # expert-parallel all-to-all inside shard_map
    mesh: Any = None  # Mesh for shard_map regions (None on CPU smoke paths)

    def constrain(self, x: jax.Array, axes: tuple) -> jax.Array:
        try:
            return jax.lax.with_sharding_constraint(x, P(*axes))
        except (ValueError, RuntimeError):
            return x  # outside a mesh context (CPU smoke tests)


# ---- params -------------------------------------------------------------------


def attn_defs(cfg: ArchConfig, cross: bool = False) -> PyTree:
    """Projection params in FLAT (d, H*Dh) layout.

    Flat layouts keep every sharded dim divisible by the mesh axis for any
    head count (H*Dh is a multiple of 64); heads are split on ACTIVATIONS
    (after the projection), where GSPMD may pad non-divisible head counts
    freely. Explicit jit argument shardings have a hard divisibility rule —
    this layout is what satisfies it for all ten archs.
    """
    d, h, k, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = pdt(cfg)
    defs = {
        "wq": ParamDef((d, h * dh), dt, ("data", "model")),
        "wk": ParamDef((d, k * dh), dt, ("data", "model")),
        "wv": ParamDef((d, k * dh), dt, ("data", "model")),
        "wo": ParamDef((h * dh, d), dt, ("model", "data")),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((h * dh,), dt, ("model",), "zeros")
        defs["bk"] = ParamDef((k * dh,), dt, ("model",), "zeros")
        defs["bv"] = ParamDef((k * dh,), dt, ("model",), "zeros")
    return defs


def cache_defs(
    cfg: ArchConfig,
    spec: BlockSpec,
    batch: int,
    max_len: int,
    policy: ShardingPolicy,
) -> PyTree:
    """KV-cache ParamDefs for one attention block (decode path input)."""
    k, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    if spec.mixer is Mixer.LOCAL_ATTN:
        length = min(max_len, spec.window)  # ring buffer
        seq_ax = None  # ring buffers are short; never sharded on seq
        feat_ax = (policy.heads or "model") if policy.kv_seq is None else None
    else:
        length = max_len
        seq_ax = policy.kv_seq
        # one mesh axis per spec: when seq takes an axis, features stay
        # local; otherwise the flat K*Dh dim takes 'model' (always a
        # multiple of 16 in flat layout) so prefill caches never replicate
        feat_ax = (policy.heads or "model") if seq_ax is None else None
    dt = jnp.dtype(cfg.activation_dtype)
    # flat (B, L, K*Dh) layout: divisible for any kv-head count (see attn_defs)
    ax = (policy.batch if batch > 1 else None, seq_ax, feat_ax)
    return {
        "k": ParamDef((batch, length, k * dh), dt, ax, "zeros"),
        "v": ParamDef((batch, length, k * dh), dt, ax, "zeros"),
    }


# ---- masks ---------------------------------------------------------------------


def _mask(
    q_pos: jax.Array,  # (Sq,)
    k_pos: jax.Array,  # (Skv,)
    causal: bool,
    window: Optional[int],
    prefix_len: Optional[int],
    k_valid: Optional[jax.Array] = None,  # (Skv,) extra validity (ring bufs)
) -> jax.Array:
    """(Sq, Skv) boolean allow-mask."""
    qp = q_pos[:, None]
    kp = k_pos[None, :]
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        c = kp <= qp
        if prefix_len is not None:
            c = c | (kp < prefix_len)
        m = m & c
    if window is not None:
        m = m & (qp - kp < window)
    if k_valid is not None:
        m = m & k_valid[None, :]
    return m


# ---- cores ---------------------------------------------------------------------


@jax.named_scope(SCOPES[0])
def _dense_core(q, kv_k, kv_v, mask) -> jax.Array:
    """q (B,Sq,K,G,Dh), k/v (B,Skv,K,Dh), mask (Sq,Skv) -> (B,Sq,K,G,Dh)."""
    dh = q.shape[-1]
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    logits = jnp.einsum(
        "bqkgd,bckd->bqkgc", q.astype(jnp.float32), kv_k.astype(jnp.float32)
    ) * scale
    logits = jnp.where(mask[None, :, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bqkgc,bckd->bqkgd", probs, kv_v.astype(jnp.float32))
    return out.astype(q.dtype)


def _online_update(carry, logits, v_chunk):
    """Online-softmax accumulation. carry = (m, l, acc).

    The output does not depend on the running max, so no gradient is taken
    through it: the backward pass then keeps no copy of the logits for the
    max, only the probabilities."""
    m, l, acc = carry
    m_new = jax.lax.stop_gradient(jnp.maximum(m, jnp.max(logits, axis=-1)))
    correction = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new[..., None])
    l_new = l * correction + jnp.sum(p, axis=-1)
    acc_new = acc * correction[..., None] + jnp.einsum(
        "bqkgc,bckd->bqkgd", p, v_chunk.astype(jnp.float32)
    )
    return m_new, l_new, acc_new


@jax.named_scope(SCOPES[0])
def _chunked_core(
    q,  # (B, Sq, K, G, Dh)
    kv_k,
    kv_v,  # (B, Skv, K, Dh)
    q_offset: int,
    causal: bool,
    window: Optional[int],
    prefix_len: Optional[int],
    chunk: int = _DEFAULT_CHUNK,
) -> jax.Array:
    """XLA flash: q-chunk outer scan (remat), kv-chunk inner scan."""
    b, sq, kh, g, dh = q.shape
    skv = kv_k.shape[1]
    qc = _divisor_chunk(sq, chunk)
    kc = min(chunk, skv)
    kv_pad = (-skv) % kc
    if kv_pad:  # non-multiple KV length (e.g. whisper's 1500-frame encoder)
        kv_k = jnp.pad(kv_k, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
        kv_v = jnp.pad(kv_v, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
    n_q, n_k = sq // qc, (skv + kv_pad) // kc
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)

    kv_ks = kv_k.reshape(b, n_k, kc, kh, dh).swapaxes(0, 1)
    kv_vs = kv_v.reshape(b, n_k, kc, kh, dh).swapaxes(0, 1)

    @jax.checkpoint
    def q_chunk_body(qi, q_c):
        q32 = q_c.astype(jnp.float32)
        q_pos = q_offset + qi * qc + jnp.arange(qc)

        def kv_body(carry, xs):
            ki, k_c, v_c = xs
            k_pos = ki * kc + jnp.arange(kc)
            logits = jnp.einsum("bqkgd,bckd->bqkgc", q32,
                                k_c.astype(jnp.float32)) * scale
            allow = _mask(q_pos, k_pos, causal, window, prefix_len,
                          k_valid=k_pos < skv)
            logits = jnp.where(allow[None, :, None, None, :], logits, NEG_INF)
            return _online_update(carry, logits, v_c), None

        init = (
            jnp.full((b, qc, kh, g), NEG_INF, jnp.float32),
            jnp.zeros((b, qc, kh, g), jnp.float32),
            jnp.zeros((b, qc, kh, g, dh), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            kv_body, init, (jnp.arange(n_k), kv_ks, kv_vs)
        )
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    qs = q.reshape(b, n_q, qc, kh, g, dh).swapaxes(0, 1)
    out = jax.lax.map(lambda xs: q_chunk_body(xs[0], xs[1]),
                      (jnp.arange(n_q), qs))
    return out.swapaxes(0, 1).reshape(b, sq, kh, g, dh)


@jax.named_scope(SCOPES[0])
def _kv_chunked_core(
    q,  # (B, Sq, K, G, Dh)
    kv_k,
    kv_v,  # (B, Skv, K, Dh)
    q_offset: int,
    causal: bool,
    window: Optional[int],
    prefix_len: Optional[int],
    chunk: int = _DEFAULT_CHUNK,
) -> jax.Array:
    """Online-softmax over KV chunks with the FULL q kept as one tensor.

    Unlike ``_chunked_core`` this never slices the sequence dim of q, so a
    sequence-parallel sharding of q survives the whole computation — the
    scan-over-q-chunks variant would dynamic-slice a sharded dim, which
    GSPMD resolves by replicating every chunk (16x waste). Used when q is
    seq-sharded (prefill of archs whose head count cannot shard over
    'model'). Memory is O(Sq_local * chunk) for the logits of one kv step.
    Causal upper-triangle blocks are masked, not skipped (XLA fallback; the
    Pallas kernel skips them on real TPUs).
    """
    b, sq, kh, g, dh = q.shape
    skv = kv_k.shape[1]
    kc = min(chunk, skv)
    kv_pad = (-skv) % kc
    if kv_pad:
        kv_k = jnp.pad(kv_k, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
        kv_v = jnp.pad(kv_v, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
    n_k = (skv + kv_pad) // kc
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    q32 = q.astype(jnp.float32)
    q_pos = q_offset + jnp.arange(sq)

    kv_ks = kv_k.reshape(b, n_k, kc, kh, dh).swapaxes(0, 1)
    kv_vs = kv_v.reshape(b, n_k, kc, kh, dh).swapaxes(0, 1)

    @jax.checkpoint
    def kv_body(carry, xs):
        ki, k_c, v_c = xs
        k_pos = ki * kc + jnp.arange(kc)
        logits = jnp.einsum(
            "bqkgd,bckd->bqkgc", q32, k_c.astype(jnp.float32)
        ) * scale
        allow = _mask(q_pos, k_pos, causal, window, prefix_len,
                      k_valid=k_pos < skv)
        logits = jnp.where(allow[None, :, None, None, :], logits, NEG_INF)
        return _online_update(carry, logits, v_c), None

    init = (
        jnp.full((b, sq, kh, g), NEG_INF, jnp.float32),
        jnp.zeros((b, sq, kh, g), jnp.float32),
        jnp.zeros((b, sq, kh, g, dh), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(
        kv_body, init, (jnp.arange(n_k), kv_ks, kv_vs)
    )
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


@jax.named_scope(SCOPES[0])
def _banded_core(
    q,  # (B, Sq, K, G, Dh)
    kv_k,
    kv_v,
    q_offset: int,
    window: int,
    chunk: int = _DEFAULT_CHUNK,
) -> jax.Array:
    """Sliding-window attention via one static KV band per Q chunk.

    For Q chunk starting at s, only positions [s - window + 1, s + qc) can be
    attended; we dynamic-slice a band of width (window + qc) and run a dense
    masked core on it: flops O(Sq * (window + chunk)) instead of O(Sq * Skv).
    """
    b, sq, kh, g, dh = q.shape
    skv = kv_k.shape[1]
    qc = _divisor_chunk(sq, chunk)
    n_q = sq // qc
    band = min(window + qc, skv)

    @jax.checkpoint
    def q_chunk_body(qi, q_c):
        start_q = qi * qc
        band_start = jnp.clip(start_q + q_offset - window + 1, 0, skv - band)
        k_band = jax.lax.dynamic_slice_in_dim(kv_k, band_start, band, axis=1)
        v_band = jax.lax.dynamic_slice_in_dim(kv_v, band_start, band, axis=1)
        q_pos = q_offset + start_q + jnp.arange(qc)
        k_pos = band_start + jnp.arange(band)
        allow = _mask(q_pos, k_pos, True, window, None)
        return _dense_core(q_c, k_band, v_band, allow)

    qs = q.reshape(b, n_q, qc, kh, g, dh).swapaxes(0, 1)
    out = jax.lax.map(lambda xs: q_chunk_body(xs[0], xs[1]),
                      (jnp.arange(n_q), qs))
    return out.swapaxes(0, 1).reshape(b, sq, kh, g, dh)


def _flash_block(s: int, window: Optional[int]) -> int:
    """The flash kernel's one block size (q, kv, compute, forward and
    backward), from the sequence and the window.  512 where a window is set:
    a query block then visits 1.5 windows of keys, not 2 as at 1,024 (on a
    TPU v5e, forward and backward of a 1,024 window at 2 x 8,192, 32 heads:
    17.1 ms at 512, 19.1 at 1,024).  1,024 for a full causal layer, where
    larger blocks keep the MXU fed (2 x 16,384, 24 heads: 107.0 ms at
    1,024, 134.5 at 512).  At most the sequence."""
    return min(s, 512 if window is not None else 1024)


def _flash_kernel(s: int, heads: int, window: Optional[int]):
    """The splash kernel for one example: causal, or causal within the window
    (``_mask``'s ``qp - kp < window``, ``kp <= qp``).  The masks' block
    tables are computed once per shape (the library caches them)."""
    if window is None:
        mask = splash.CausalMask((s, s))
    else:
        mask = splash.LocalMask((s, s), (window - 1, 0), offset=0)
    blk = _flash_block(s, window)
    blocks = splash.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        block_q_dq=blk, block_kv_dq=blk)
    return splash.make_splash_mha(
        splash.MultiHeadMask([mask] * heads), block_sizes=blocks,
        head_shards=1, q_seq_shards=1,
        interpret=jax.default_backend() != "tpu")


@jax.named_scope(SCOPES[0])
def _flash_core(
    q,  # (B, S, K, G, Dh)
    kv_k,
    kv_v,  # (B, S, K, Dh)
    window: Optional[int],
) -> jax.Array:
    """Causal self-attention (windowed where ``window`` is set) through the
    splash kernel, one example at a time.  Query heads are grouped KV-major,
    head k*G + g reading KV head k, as the kernel's GQA expects.  The kernel
    takes no scale, so q is scaled by 1/sqrt(Dh) first (in fp32, rounded
    once to the activation dtype)."""
    b, s, kh, g, dh = q.shape
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qs = qs.reshape(b, s, kh * g, dh).transpose(0, 2, 1, 3)
    out = jax.vmap(_flash_kernel(s, kh * g, window))(
        qs, kv_k.transpose(0, 2, 1, 3), kv_v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3).reshape(b, s, kh, g, dh)


def pick_core(
    *,
    backend: str,
    s: int,
    head_dim: int,
    window: Optional[int],
    causal: bool,
    prefix_len: Optional[int],
    seq_sharded: bool,
    n_devices: int,
) -> str:
    """The core of one train/prefill self-attention call, from what the
    call can observe: ``flash``, ``dense``, ``chunked``, ``kv_chunked`` or
    ``banded``.  (Decode and cross-attention keep cores of their own.)

    The flash kernel takes causal attention (full or windowed, no
    prefix-LM) past the dense threshold, on a TPU, where the head dim and
    the sequence are whole lane tiles and blocks, and where the operands
    are not partitioned (a pallas_call on a sharded mesh would be
    replicated).  Everything else keeps its XLA core."""
    if (backend == "tpu" and s > _DENSE_MAX_SEQ and causal
            and prefix_len is None and n_devices == 1
            and head_dim % _LANES == 0 and s % _flash_block(s, window) == 0):
        return "flash"
    if window is not None and s > window:
        return "banded"
    if s <= _DENSE_MAX_SEQ:
        return "dense"
    if seq_sharded:
        return "kv_chunked"
    return "chunked"


# ---- block application ------------------------------------------------------------


def _project_qkv(cfg, p, x, kv_x=None):
    """x (B,S,D) -> q (B,S,H,Dh), k/v (B,Skv,K,Dh). Weights are flat."""
    h, k_heads, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    kv_x = x if kv_x is None else kv_x
    q = x @ p["wq"].astype(x.dtype)
    k = kv_x @ p["wk"].astype(x.dtype)
    v = kv_x @ p["wv"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    b, s = x.shape[0], x.shape[1]
    skv = kv_x.shape[1]
    return (
        q.reshape(b, s, h, dh),
        k.reshape(b, skv, k_heads, dh),
        v.reshape(b, skv, k_heads, dh),
    )


def _group(q, n_kv):
    b, s, h, dh = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, dh)


def _ungroup(o):
    b, s, k, g, dh = o.shape
    return o.reshape(b, s, k * g, dh)


def attn_apply(
    cfg: ArchConfig,
    spec: BlockSpec,
    p: PyTree,
    x: jax.Array,
    *,
    policy: ShardingPolicy,
    positions: Optional[jax.Array] = None,
    cache: Optional[PyTree] = None,
    decode_pos: Optional[jax.Array] = None,
    prefix_len: Optional[int] = None,
    cross_kv: Optional[jax.Array] = None,
    causal: bool = True,
    chunk: int = _DEFAULT_CHUNK,
) -> tuple[jax.Array, Optional[PyTree]]:
    """One attention block. Returns (out, new_cache).

    Modes:
      * train/prefill: ``cache is None`` (train) or cache returned filled
        (prefill): full-sequence x, the core ``pick_core`` names.
      * decode: ``decode_pos`` given, x is (B, 1, D), cache is read+updated.
      * cross: ``cross_kv`` is the encoder output (B, Senc, D); no cache
        mutation (cross KV is precomputed into the cache at prefill).
    """
    b, s, d = x.shape
    n_kv = cfg.n_kv_heads
    window = spec.window if spec.mixer is Mixer.LOCAL_ATTN else None

    if positions is None:
        base = 0 if decode_pos is None else decode_pos
        positions = base + jnp.arange(s)[None, :]
        positions = jnp.broadcast_to(positions, (b, s))

    q, k, v = _project_qkv(cfg, p, x, kv_x=cross_kv)
    if spec.rope_base is not None and cross_kv is None:
        q = rope(q, positions, spec.rope_base)
        k = rope(k, positions, spec.rope_base)
    # inside attention, seq and heads cannot both take 'model': heads win
    # (Megatron layout — the seq constraint re-applies at the block output)
    q_seq_ax = None if policy.heads is not None else policy.seq
    q = policy.constrain(q, (policy.batch, q_seq_ax, policy.heads, None))
    decode = decode_pos is not None and cache is not None
    kind = None if decode or cross_kv is not None else pick_core(
        backend=jax.default_backend(), s=s, head_dim=cfg.resolved_head_dim,
        window=window, causal=causal, prefix_len=prefix_len,
        seq_sharded=policy.heads is None and policy.seq is not None,
        n_devices=1 if policy.mesh is None else policy.mesh.size)
    # Head-sharded execution (train/prefill): repeat K/V up to the full head
    # count so every attention einsum has the same head dim — GSPMD then
    # pad-shards H over 'model' uniformly. Without this, the (K, G) grouped
    # layout forces an 8-way <-> 16-way reshard per einsum, which the SPMD
    # partitioner resolves by involuntary full rematerialization (replicating
    # whole activations). KV-cache layouts keep the un-repeated GQA K.
    k_cache_src, v_cache_src = k, v
    n_kv_eff = n_kv
    # the flash kernel reads each KV head for its group of query heads and
    # runs unpartitioned: it takes K/V as they are
    if (policy.heads is not None and n_kv < cfg.n_heads
            and decode_pos is None and kind != "flash"):
        g_rep = cfg.n_heads // n_kv
        k = jnp.repeat(k, g_rep, axis=2)
        v = jnp.repeat(v, g_rep, axis=2)
        n_kv_eff = cfg.n_heads
    if policy.heads is not None and decode_pos is None:
        k = policy.constrain(k, (policy.batch, None, policy.heads, None))
        v = policy.constrain(v, (policy.batch, None, policy.heads, None))
    qg = _group(q, n_kv_eff)

    def _flat(t):  # (B, L, K, Dh) -> cache layout (B, L, K*Dh)
        return t.reshape(t.shape[0], t.shape[1], -1)

    def _unflat(t):  # cache layout -> (B, L, K, Dh)
        return t.reshape(t.shape[0], t.shape[1], n_kv, cfg.resolved_head_dim)

    new_cache = cache
    if decode:
        # -- decode: write k/v at decode_pos (ring for local), dense core
        cache_len = cache["k"].shape[1]
        if window is not None:
            slot = decode_pos % cache_len
        else:
            slot = decode_pos
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], _flat(k), slot, axis=1
        )
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], _flat(v), slot, axis=1
        )
        new_cache = {"k": ck, "v": cv}
        idx = jnp.arange(cache_len)
        if window is not None:
            # slot s holds absolute position p = decode_pos - ((decode_pos - s) mod L)
            k_pos = decode_pos - jnp.mod(decode_pos - idx, cache_len)
            k_valid = k_pos >= 0
        else:
            k_pos = idx
            k_valid = idx <= decode_pos
        allow = _mask(
            positions[0], k_pos, causal, window, prefix_len, k_valid
        )
        out = _dense_core(qg, _unflat(ck), _unflat(cv), allow)
    elif cross_kv is not None:
        if s <= _DENSE_MAX_SEQ:
            allow = jnp.ones((s, cross_kv.shape[1]), bool)
            out = _dense_core(qg, k, v, allow)
        else:
            # long decoder sequences: chunked core, non-causal, no mask —
            # keeps cross-attn memory O(chunk * Senc) instead of O(Sq * Senc)
            out = _chunked_core(qg, k, v, 0, False, None, None, chunk)
    else:
        # -- train / prefill over the full sequence
        if cache is not None:  # prefill: persist computed K/V (GQA layout)
            k_w, v_w = k_cache_src, v_cache_src
            cache_len = cache["k"].shape[1]
            if window is not None and s > cache_len:
                # ring buffer keeps the LAST `cache_len` positions
                tail_k, tail_v = _flat(k_w)[:, -cache_len:], _flat(v_w)[:, -cache_len:]
                # place position p at slot p % cache_len
                pos_tail = jnp.arange(s - cache_len, s)
                slots = jnp.mod(pos_tail, cache_len)
                ck = jnp.zeros_like(cache["k"]).at[:, slots].set(tail_k)
                cv = jnp.zeros_like(cache["v"]).at[:, slots].set(tail_v)
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], _flat(k_w)[:, :cache_len], 0, axis=1
                )
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], _flat(v_w)[:, :cache_len], 0, axis=1
                )
            new_cache = {"k": ck, "v": cv}
        if kind == "flash":
            out = _flash_core(qg, k, v, window)
        elif kind == "banded":
            out = _banded_core(qg, k, v, 0, window, chunk)
        elif kind == "dense":
            allow = _mask(
                jnp.arange(s), jnp.arange(s), causal, window, prefix_len
            )
            out = _dense_core(qg, k, v, allow)
        elif kind == "kv_chunked":
            # q is sequence-sharded and heads cannot take the 'model' axis:
            # the q-chunk scan would slice a sharded dim (replication) —
            # keep q whole and stream KV chunks instead
            out = _kv_chunked_core(qg, k, v, 0, causal, window, prefix_len,
                                   chunk)
        else:
            out = _chunked_core(qg, k, v, 0, causal, window, prefix_len, chunk)

    o = _ungroup(out)  # (B, S, H, Dh)
    o_flat = o.reshape(o.shape[0], o.shape[1], -1)
    y = o_flat @ p["wo"].astype(x.dtype)
    # Megatron-SP: reduce-scatter the TP-partial output back onto the
    # sequence axis (GSPMD emits it from this constraint pair)
    y = policy.constrain(y, (policy.batch, policy.seq, None))
    return y.astype(x.dtype), new_cache
