"""Mixture-of-Experts FF with top-k routing and capacity-based dispatch.

Dispatch is the cumsum/scatter formulation (no sort): for each (token, k)
assignment we compute the token's position within its expert's capacity
buffer via a cumulative sum over one-hot expert indicators, drop overflow,
scatter into an (G, E, C, d) buffer, run the expert FFs as one grouped
einsum, and gather back with the softmax gate weights. FLOPs are therefore
O(top_k * capacity_factor * N * d * f) — the *active*-expert cost, not the
all-experts dense cost, which keeps the roofline compute term honest
(DESIGN.md §4: MoE is the paper's sparse-gradient regime analogue).

Sharding: dispatch is GROUP-LOCAL. Tokens are reshaped into G groups, one
per data-parallel shard (policy.moe_groups == product of batch-axis sizes),
so capacity buffers shard over the batch axes and the (group -> expert)
exchange lowers to the all-to-all GSPMD materializes at the expert-parallel
boundary. A single global capacity buffer would be a (E, n*cap/E, d) scatter
target whose sharding GSPMD cannot infer — group-locality is what keeps the
MoE memory footprint per-chip O(local_tokens * d) at mixtral scale.

Expert placement: experts are expert-parallel over 'model' when the expert
count divides the axis (phi3.5: 16e); otherwise each expert is tensor-sliced
over (data, model) (mixtral: 8e < 16) — see moe_defs.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.params import ParamDef

PyTree = Any


EP_TARGET = 16  # 'model' axis size of the production meshes


def expert_split(cfg: ArchConfig) -> int:
    """f-dim split factor turning E physical experts into E*split VIRTUAL
    experts so the expert dim always fills the EP axis (mixtral: 8e x 2)."""
    e = cfg.moe.n_experts
    if e % EP_TARGET == 0:
        return 1
    assert EP_TARGET % e == 0, (e, EP_TARGET)
    return EP_TARGET // e


def moe_defs(cfg: ArchConfig) -> PyTree:
    """Expert weights in VIRTUAL-expert layout: (E*split, d, f/split) with
    the virtual-expert dim expert-parallel over 'model'.

    When E < EP_TARGET each physical expert is split into ``split`` f-slices
    that behave as separate experts sharing the routing decision (SwiGLU and
    the down-projection are exactly f-separable: concat of slice outputs ==
    the unsplit output summed over slices). This keeps the (G, E', C, d)
    dispatch buffer shardable over 'model' for every expert count — a
    replicated buffer would force a full all-reduce of the buffer at the
    scatter (measured 101s collective term for mixtral before this fix;
    EXPERIMENTS.md §Perf)."""
    assert cfg.moe is not None
    e, d, f = cfg.moe.n_experts, cfg.d_model, cfg.d_ff
    s = expert_split(cfg)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "router": ParamDef((d, e), jnp.float32, (None, None)),
        "w_gate": ParamDef((e * s, d, f // s), dt, ("model", "data", None)),
        "w_up": ParamDef((e * s, d, f // s), dt, ("model", "data", None)),
        "w_down": ParamDef((e * s, f // s, d), dt, ("model", None, "data")),
    }


def capacity(n_tokens: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    c = int(math.ceil(n_tokens * m.top_k * m.capacity_factor / m.n_experts))
    return max(8, ((c + 7) // 8) * 8)  # pad to a sublane multiple


def moe_apply(
    cfg: ArchConfig, p: PyTree, x: jax.Array, policy=None
) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss).

    aux_loss is the standard load-balance term (mean gate prob * token
    density per expert, scaled by E) — returned so the train loop can add it.
    """
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    e, k = m.n_experts, m.top_k
    G = getattr(policy, "moe_groups", 1) if policy is not None else 1
    if n % G != 0:
        G = 1
    ng = n // G
    xt = x.reshape(G, ng, d)
    group_ax = getattr(policy, "moe_group_ax", None) if policy else None
    token_ax = getattr(policy, "moe_token_ax", None) if policy else None
    ep_ax = getattr(policy, "moe_ep_ax", None) if policy else None
    if policy is not None:
        xt = policy.constrain(xt, (group_ax, token_ax, None))

    # -- routing (fp32)
    logits = xt.astype(jnp.float32) @ p["router"]  # (G, ng, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)  # (G, ng, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    # -- load-balance aux (Switch-style), over the full global batch
    density = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_ids, e, dtype=jnp.float32), axis=2),
        axis=(0, 1),
    )  # (E,) fraction of tokens routed to each expert (summed over k)
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(density / k * mean_prob)

    # -- capacity slots: position of each (token, k) within its PHYSICAL
    #    expert, computed group-locally
    c = capacity(ng, cfg)
    flat_e = expert_ids.reshape(G, ng * k)  # arrival order (token-major)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (G, ng*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=1) - onehot  # exclusive cumsum
    slot = jnp.sum(pos_in_e * onehot, axis=-1)  # (G, ng*k)
    keep = slot < c

    # -- virtual experts: each (token, k) assignment fans out to the
    #    ``split`` f-slices of its expert, same slot in each (moe_defs)
    split = expert_split(cfg)
    e_virt = e * split
    na = ng * k * split  # assignments per group
    tok_idx = jnp.repeat(jnp.arange(ng), k * split)  # (na,)
    flat_ev = (
        flat_e[:, :, None] * split + jnp.arange(split)[None, None, :]
    ).reshape(G, na)
    slot_v = jnp.repeat(slot, split, axis=1)
    keep_v = jnp.repeat(keep, split, axis=1)
    gates_flat = gate_vals.reshape(G, ng * k)

    safe_slot = jnp.where(keep_v, slot_v, c - 1)
    w_assign = jnp.repeat(
        gates_flat * keep.astype(jnp.float32), split, axis=1
    )
    # Weights are STORED 2D-sharded (ZeRO-3); for compute they are either
    # gathered in full (train: groups cover every axis) or re-sharded onto
    # d_ff over 'model' (prefill: groups only cover 'data') — policy.moe_f_ax
    w_gate, w_up, w_down = p["w_gate"], p["w_up"], p["w_down"]
    f_ax = getattr(policy, "moe_f_ax", None) if policy else None
    if policy is not None and f_ax is not None:
        w_gate = policy.constrain(w_gate, (None, None, f_ax))
        w_up = policy.constrain(w_up, (None, None, f_ax))
        w_down = policy.constrain(w_down, (None, f_ax, None))

    a2a = bool(getattr(policy, "moe_a2a", False)) if policy else False

    def dispatch_ff_combine(xt_, ev_, ss_, kv_, wa_, wg_, wu_, wd_):
        """Dispatch -> expert FF -> combine over a (local) block of groups.

        Pure per-group code (vmapped scatter/gather). Under shard_map the
        group dim is manual-sharded, so dispatch is structurally chip-local.

        When ``a2a`` is set (expert parallelism): the local capacity buffers
        are exchanged over 'model' with all_to_all, the expert FF runs on
        RESIDENT weight slices (E'/tp experts per chip — no per-layer
        weight gather), and a second all_to_all returns the outputs. This
        moves tokens (~0.2 GB/chip/layer) instead of expert weights
        (~4.8 GB/layer for mixtral) — EXPERIMENTS.md §Perf iteration 2.
        """
        contrib = jnp.where(kv_[..., None], xt_[:, tok_idx], 0).astype(
            x.dtype
        )

        def scatter_group(ev_g, slot_g, contrib_g):
            return jnp.zeros((e_virt, c, d), x.dtype).at[ev_g, slot_g].add(
                contrib_g, mode="drop"
            )

        buf = jax.vmap(scatter_group)(ev_, ss_, contrib)  # (gl, E', c, d)
        if a2a:
            gl = buf.shape[0]
            # (gl, E', c, d) -> exchange expert shards over 'model':
            # each chip ends with its E'/tp experts x (tp senders * c) slots
            sent = buf.reshape(gl * e_virt, c, d)
            recv = jax.lax.all_to_all(
                sent, "model", split_axis=0, concat_axis=1, tiled=True
            )  # (gl * E'/tp, tp * c, d)
            fbuf = recv.reshape(gl, -1, recv.shape[1], d)  # (gl,E'loc,tp*c,d)
        else:
            fbuf = buf
        gg = jnp.einsum("gecd,edf->gecf", fbuf, wg_.astype(x.dtype))
        uu = jnp.einsum("gecd,edf->gecf", fbuf, wu_.astype(x.dtype))
        h = (jax.nn.silu(gg) * uu).astype(x.dtype)
        out_fbuf = jnp.einsum("gecf,efd->gecd", h, wd_.astype(x.dtype))
        if a2a:
            gl = out_fbuf.shape[0]
            sent_back = out_fbuf.reshape(gl * out_fbuf.shape[1],
                                         out_fbuf.shape[2], d)
            back = jax.lax.all_to_all(
                sent_back, "model", split_axis=1, concat_axis=0, tiled=True
            )  # (gl * E', c, d)
            out_buf = back.reshape(gl, e_virt, c, d)
        else:
            out_buf = out_fbuf
        gathered = jax.vmap(lambda ob, ev, sl: ob[ev, sl])(out_buf, ev_, ss_)
        weighted = gathered * wa_[..., None].astype(x.dtype)
        return jnp.sum(weighted.reshape(-1, ng, k * split, d), axis=2)

    mesh = getattr(policy, "mesh", None) if policy is not None else None
    manual = _axes_set(group_ax)
    if mesh is not None and manual and G > 1:
        # shard_map over the group axes: GSPMD cannot partition the batched
        # capacity scatter/gather (it replicates the buffer and all-reduces
        # token-sized gradients — measured 346s/step of collectives for
        # mixtral); making group-locality STRUCTURAL removes every dispatch
        # collective. Expert weights enter replicated over the group axes
        # (their ZeRO-3 gather is emitted once, outside), and stay auto-
        # sharded on any axis not in `manual` (prefill keeps f over 'model').
        from jax.sharding import PartitionSpec as P

        # a2a mode: expert weights stay RESIDENT, sharded over 'model' on
        # the virtual-expert dim (the ZeRO gather over 'data' still happens
        # outside, but the 16x larger 'model' gather disappears)
        w_spec = P("model", None, None) if a2a else P(None, None, None)
        out = jax.shard_map(
            dispatch_ff_combine,
            mesh=mesh,
            in_specs=(
                P(group_ax, token_ax, None),
                P(group_ax, None),
                P(group_ax, None),
                P(group_ax, None),
                P(group_ax, None),
                w_spec,
                w_spec,
                w_spec,
            ),
            out_specs=P(group_ax, token_ax, None),
            axis_names=manual,
            check_vma=False,
        )(xt, flat_ev, safe_slot, keep_v, w_assign, w_gate, w_up, w_down)
    else:
        out = dispatch_ff_combine(
            xt, flat_ev, safe_slot, keep_v, w_assign, w_gate, w_up, w_down
        )
    return out.reshape(b, s, d), aux.astype(jnp.float32)


def _axes_set(group_ax) -> set:
    if group_ax is None:
        return set()
    if isinstance(group_ax, str):
        return {group_ax}
    return set(group_ax)
