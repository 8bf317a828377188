"""The composable LM stack: scan-over-superblocks transformer assembly.

One class drives all ten assigned architectures: dense decoders (phi4, qwen,
starcoder2), local:global patterns (gemma3), MoE (mixtral, phi3.5-moe),
hybrid recurrent (recurrentgemma), xLSTM stacks, encoder-decoder (whisper)
and VLM-prefix models (paligemma). The depth dimension lowers as one
``lax.scan`` per (superblock, repeats) group, so HLO size — and therefore
512-device compile time — is O(pattern length), not O(depth).

API (all pure functions over pytrees):
  * ``param_defs()`` / ``init(key)`` / ``cache_defs(batch, max_len)``
  * ``train_loss(params, batch)``             -> (loss, metrics)
  * ``prefill(params, cache, batch)``         -> (last_logits, cache)
  * ``decode_step(params, cache, batch, pos)``-> (logits, cache)
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import params as pdefs
from repro.models.attention import (
    ShardingPolicy,
    attn_apply,
    attn_defs,
    cache_defs as attn_cache_defs,
)
from repro.models.config import ArchConfig, BlockSpec, FF, Mixer
from repro.models.layers import (
    adt,
    chunked_softmax_xent,
    embed_apply,
    embed_defs,
    ff_apply,
    ff_defs,
    norm_apply,
    norm_defs,
    sinusoidal_positions,
    unembed_apply,
)
from repro.models.moe import held_experts, moe_apply, moe_defs
from repro.models.rglru import rglru_apply, rglru_cache_defs, rglru_defs
from repro.models.xlstm import (
    mlstm_apply,
    mlstm_cache_defs,
    mlstm_defs,
    slstm_apply,
    slstm_cache_defs,
    slstm_defs,
)

PyTree = Any

_ATTN_MIXERS = (Mixer.GLOBAL_ATTN, Mixer.LOCAL_ATTN, Mixer.CROSS_ATTN)


@dataclasses.dataclass(frozen=True)
class LM:
    cfg: ArchConfig
    policy: ShardingPolicy = dataclasses.field(default_factory=ShardingPolicy)

    # ---- parameter declaration ------------------------------------------------

    def _block_defs(self, spec: BlockSpec) -> PyTree:
        cfg = self.cfg
        d: dict[str, Any] = {"norm1": norm_defs(cfg)}
        if spec.mixer in _ATTN_MIXERS:
            d["mixer"] = attn_defs(cfg, cross=spec.mixer is Mixer.CROSS_ATTN)
        elif spec.mixer is Mixer.RGLRU:
            d["mixer"] = rglru_defs(cfg)
        elif spec.mixer is Mixer.MLSTM:
            d["mixer"] = mlstm_defs(cfg)
        elif spec.mixer is Mixer.SLSTM:
            d["mixer"] = slstm_defs(cfg)
        else:
            raise ValueError(spec.mixer)
        if spec.ff is FF.MOE:
            d["norm2"] = norm_defs(cfg)
            d["ff"] = moe_defs(cfg)
        elif spec.ff is not FF.NONE:
            d["norm2"] = norm_defs(cfg)
            d["ff"] = ff_defs(cfg, spec.ff)
        return d

    def _superblock_defs(self, superblock: tuple[BlockSpec, ...]) -> PyTree:
        return {f"b{i}": self._block_defs(s) for i, s in enumerate(superblock)}

    def param_defs(self) -> PyTree:
        cfg = self.cfg
        defs: dict[str, Any] = {"embed": embed_defs(cfg)}
        defs["groups"] = [
            pdefs.stack(self._superblock_defs(sb), reps)
            for sb, reps in cfg.groups
        ]
        defs["final_norm"] = norm_defs(cfg)
        if cfg.encoder is not None and cfg.family == "audio":
            # whisper-style audio encoder: full bidirectional attention
            enc_sb = (BlockSpec(Mixer.GLOBAL_ATTN, FF.GELU, rope_base=None),)
            defs["encoder"] = {
                "groups": [
                    pdefs.stack(self._superblock_defs(enc_sb), cfg.encoder.n_layers)
                ],
                "final_norm": norm_defs(cfg),
            }
        return defs

    def init(self, key: jax.Array) -> PyTree:
        return pdefs.materialize(self.param_defs(), key)

    def n_params(self) -> int:
        return pdefs.count_params(self.param_defs())

    def n_active_params(self) -> int:
        """MoE-aware active parameter count (for MODEL_FLOPS = 6*N_active*D)."""
        cfg = self.cfg
        total = self.n_params()
        if cfg.moe is None:
            return total
        # a token's k assignments fall on the experts held here in the share
        # held / n_experts (all of them where the layer holds every expert)
        held = held_experts(cfg)
        moe_total = 0
        moe_active = 0
        for sb, reps in cfg.groups:
            for s in sb:
                if s.ff is FF.MOE:
                    per_expert = 3 * cfg.d_model * cfg.d_ff
                    moe_total += reps * held * per_expert
                    moe_active += (reps * cfg.moe.top_k * held * per_expert
                                   // cfg.moe.n_experts)
        return total - moe_total + moe_active

    # ---- caches -----------------------------------------------------------------

    def _block_cache_defs(
        self, spec: BlockSpec, batch: int, max_len: int
    ) -> Optional[PyTree]:
        cfg, pol = self.cfg, self.policy
        if spec.mixer is Mixer.CROSS_ATTN:
            return None  # cross K/V recomputed from encoder_out each step
        if spec.mixer in _ATTN_MIXERS:
            return attn_cache_defs(cfg, spec, batch, max_len, pol)
        if spec.mixer is Mixer.RGLRU:
            return rglru_cache_defs(cfg, batch, pol)
        if spec.mixer is Mixer.MLSTM:
            return mlstm_cache_defs(cfg, batch, pol)
        if spec.mixer is Mixer.SLSTM:
            return slstm_cache_defs(cfg, batch, pol)
        return None

    def cache_defs(self, batch: int, max_len: int) -> PyTree:
        groups = []
        for sb, reps in self.cfg.groups:
            sub = {}
            for i, s in enumerate(sb):
                cd = self._block_cache_defs(s, batch, max_len)
                if cd is not None:
                    sub[f"b{i}"] = pdefs.stack(cd, reps)
            groups.append(sub)
        return {"groups": groups}

    def init_cache(self, batch: int, max_len: int) -> PyTree:
        defs = self.cache_defs(batch, max_len)
        return jax.tree.map(
            lambda d: jnp.zeros(d.shape, d.dtype), defs, is_leaf=pdefs.is_def
        )

    # ---- block application --------------------------------------------------------

    def _apply_block(
        self,
        spec: BlockSpec,
        p: PyTree,
        x: jax.Array,
        cache: Optional[PyTree],
        *,
        decode_pos: Optional[jax.Array],
        prefix_len: Optional[int],
        encoder_out: Optional[jax.Array],
        causal: bool,
    ) -> tuple[jax.Array, jax.Array, Optional[PyTree]]:
        cfg, pol = self.cfg, self.policy
        aux = jnp.zeros((), jnp.float32)
        h = norm_apply(cfg, p["norm1"], x)
        if spec.mixer in _ATTN_MIXERS:
            mixed, new_cache = attn_apply(
                cfg, spec, p["mixer"], h,
                policy=pol,
                cache=cache,
                decode_pos=decode_pos,
                prefix_len=prefix_len,
                cross_kv=encoder_out if spec.mixer is Mixer.CROSS_ATTN else None,
                causal=causal,
            )
        elif spec.mixer is Mixer.RGLRU:
            mixed, new_cache = rglru_apply(
                cfg, p["mixer"], h, cache, decode=decode_pos is not None,
                policy=pol,
            )
        elif spec.mixer is Mixer.MLSTM:
            mixed, new_cache = mlstm_apply(
                cfg, p["mixer"], h, cache, decode=decode_pos is not None,
                policy=pol,
            )
        elif spec.mixer is Mixer.SLSTM:
            mixed, new_cache = slstm_apply(
                cfg, p["mixer"], h, cache, decode=decode_pos is not None,
                policy=pol,
            )
        else:
            raise ValueError(spec.mixer)
        x = x + mixed

        if spec.ff is FF.MOE:
            h2 = norm_apply(cfg, p["norm2"], x)
            ff_out, aux = moe_apply(cfg, p["ff"], h2, policy=pol)
            x = x + ff_out
        elif spec.ff is not FF.NONE:
            h2 = norm_apply(cfg, p["norm2"], x)
            x = x + ff_apply(cfg, spec.ff, p["ff"], h2)
        return x, aux, new_cache

    def _run_group(
        self,
        superblock: tuple[BlockSpec, ...],
        group_params: PyTree,
        x: jax.Array,
        group_cache: Optional[PyTree],
        **kw,
    ) -> tuple[jax.Array, jax.Array, Optional[PyTree]]:
        """Scan `reps` copies of the superblock over the residual stream."""
        has_cache = group_cache is not None and len(group_cache) > 0

        pol = self.policy

        # each block is rematerialized in the backward pass, so the backward
        # holds one block's activations at a time (four expert layers'
        # dispatch buffers would not fit one chip at 8k)
        @partial(jax.checkpoint, static_argnums=(0,))
        def apply_block(spec, p, xc, c_in):
            return self._apply_block(spec, p, xc, c_in, **kw)

        def superblock_fwd(xc, p_sb, c_sb):
            """One superblock; its blocks are rematerialized in the backward
            pass so the scan saves only the (SP-sharded) residual-stream
            carry per block — O(depth * B*S*D / (dp*tp)) activation memory
            (DESIGN.md §5)."""
            aux_acc = jnp.zeros((), jnp.float32)
            new_caches = {}
            for i, spec in enumerate(superblock):
                c_in = c_sb.get(f"b{i}") if has_cache else None
                xc, aux, c_out = apply_block(spec, p_sb[f"b{i}"], xc, c_in)
                aux_acc = aux_acc + aux
                if c_out is not None and has_cache:
                    new_caches[f"b{i}"] = c_out
            # re-pin the carry to the sequence-parallel layout at the
            # superblock boundary (keeps the scan carry small per chip)
            xc = pol.constrain(xc, (pol.batch, pol.seq, None))
            return xc, aux_acc, new_caches

        def body(carry, xs):
            xc, aux_acc = carry
            p_sb, c_sb = xs if has_cache else (xs, {})
            xc, aux, new_caches = superblock_fwd(xc, p_sb, c_sb or {})
            return (xc, aux_acc + aux), (new_caches if has_cache else None)

        xs = (group_params, group_cache) if has_cache else group_params
        (x, aux), new_cache = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), xs
        )
        return x, aux, (new_cache if has_cache else None)

    # ---- full forward ---------------------------------------------------------------

    def _encode(self, params: PyTree, frames: jax.Array) -> jax.Array:
        """Whisper audio encoder over precomputed frame embeddings (stub
        frontend): sinusoidal positions + bidirectional attention stack."""
        cfg = self.cfg
        enc = params["encoder"]
        pos = sinusoidal_positions(frames.shape[1], cfg.d_model)
        x = (frames.astype(jnp.float32) + pos[None]).astype(adt(cfg))
        enc_sb = (BlockSpec(Mixer.GLOBAL_ATTN, FF.GELU, rope_base=None),)
        x, _, _ = self._run_group(
            enc_sb, enc["groups"][0], x, None,
            decode_pos=None, prefix_len=None, encoder_out=None, causal=False,
        )
        return norm_apply(cfg, enc["final_norm"], x)

    def forward(
        self,
        params: PyTree,
        tokens: jax.Array,
        *,
        cache: Optional[PyTree] = None,
        decode_pos: Optional[jax.Array] = None,
        encoder_out: Optional[jax.Array] = None,
        vision_embeds: Optional[jax.Array] = None,
    ) -> tuple[jax.Array, Optional[PyTree], jax.Array]:
        """Returns (hidden (B,S,d), new_cache, moe_aux_loss)."""
        cfg = self.cfg
        x = embed_apply(cfg, params["embed"], tokens)
        prefix_len = None
        if vision_embeds is not None:  # paligemma: prepend patch embeddings
            x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
            prefix_len = vision_embeds.shape[1] if cfg.prefix_lm else None
        x = self.policy.constrain(
            x, (self.policy.batch, self.policy.seq, None)
        )

        new_groups = []
        aux_total = jnp.zeros((), jnp.float32)
        cache_groups = cache["groups"] if cache is not None else None
        for gi, (sb, reps) in enumerate(cfg.groups):
            gc = cache_groups[gi] if cache_groups is not None else None
            x, aux, ngc = self._run_group(
                sb, params["groups"][gi], x, gc,
                decode_pos=decode_pos,
                prefix_len=prefix_len,
                encoder_out=encoder_out,
                causal=True,
            )
            aux_total = aux_total + aux
            new_groups.append(ngc if ngc is not None else (gc or {}))
        x = norm_apply(cfg, params["final_norm"], x)
        new_cache = {"groups": new_groups} if cache is not None else None
        return x, new_cache, aux_total

    # ---- entry points ------------------------------------------------------------------

    def train_loss(
        self, params: PyTree, batch: dict[str, jax.Array]
    ) -> tuple[jax.Array, dict[str, jax.Array]]:
        cfg = self.cfg
        encoder_out = None
        if cfg.family == "audio":
            encoder_out = self._encode(params, batch["frames"])
        vision = batch.get("vision_embeds")
        hidden, _, aux = self.forward(
            params, batch["tokens"], encoder_out=encoder_out,
            vision_embeds=vision,
        )
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        if vision is not None:
            hidden = hidden[:, vision.shape[1] :]  # loss over text positions only
        loss = chunked_softmax_xent(cfg, params["embed"], hidden, labels, mask)
        total = loss + 0.01 * aux
        return total, {"xent": loss, "moe_aux": aux}

    def prefill(
        self, params: PyTree, cache: PyTree, batch: dict[str, jax.Array]
    ) -> tuple[jax.Array, PyTree]:
        """Fill the KV/recurrent caches from a full prompt; return logits of
        the last position (next-token distribution) and the filled cache."""
        cfg = self.cfg
        encoder_out = None
        if cfg.family == "audio":
            encoder_out = self._encode(params, batch["frames"])
        hidden, new_cache, _ = self.forward(
            params, batch["tokens"], cache=cache, encoder_out=encoder_out,
            vision_embeds=batch.get("vision_embeds"),
        )
        logits = unembed_apply(cfg, params["embed"], hidden[:, -1:])
        return logits, new_cache

    def decode_step(
        self,
        params: PyTree,
        cache: PyTree,
        batch: dict[str, jax.Array],
        pos: jax.Array,
    ) -> tuple[jax.Array, PyTree]:
        """One-token decode: batch['tokens'] is (B, 1); pos is the absolute
        position being written (scalar int32)."""
        cfg = self.cfg
        encoder_out = batch.get("encoder_out")
        if cfg.family == "audio" and encoder_out is None:
            encoder_out = self._encode(params, batch["frames"])
        hidden, new_cache, _ = self.forward(
            params, batch["tokens"], cache=cache, decode_pos=pos,
            encoder_out=encoder_out,
        )
        logits = unembed_apply(cfg, params["embed"], hidden)
        return logits, new_cache
