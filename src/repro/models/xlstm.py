"""xLSTM blocks (arXiv:2405.04517): mLSTM (matrix memory) and sLSTM (scalar).

mLSTM is a linear-attention-style cell with per-step gates:

    C_t = f_t * C_{t-1} + i_t * (v_t k_t^T)     # (Dh, Dh) matrix memory
    n_t = f_t * n_{t-1} + i_t * k_t             # normalizer
    h_t = (C_t q_t) / max(|n_t . q_t|, 1)

Training uses the **chunkwise-parallel** form (intra-chunk quadratic with
decay mask, inter-chunk recurrent carry) — O(S * c) memory, matmul-dominated
(MXU-friendly), the TPU-native counterpart of the paper's fused CUDA kernel.
Gate simplification, documented in DESIGN.md §8: sigmoid input gates instead
of stabilized exponential gating (identical FLOP/memory profile; the
stabilizer state is an artifact of exp-gating only).

sLSTM has recurrent (h_{t-1} -> gates) connections, so it is inherently
sequential: one fp32 ``lax.scan`` over time. This is why the 7:1 mLSTM:sLSTM
pattern exists — the roofline table shows the sLSTM layers' serialization
cost directly.

Both blocks carry xLSTM's internal up/down projections (d_ff = 0 in the
assigned config: there is no separate FF block).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.params import ParamDef

PyTree = Any

_CHUNK = 256


def _axes_set(ax) -> set:
    if ax is None:
        return set()
    if isinstance(ax, str):
        return {ax}
    return set(a for a in ax if a)


def _inner(cfg: ArchConfig) -> int:
    return int(cfg.d_model * cfg.lstm_proj_factor)


# ---------------------------------------------------------------- mLSTM ------


def mlstm_defs(cfg: ArchConfig) -> PyTree:
    d = cfg.d_model
    di = _inner(cfg)
    h = cfg.n_heads
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "w_up": ParamDef((d, di), dt, ("data", "model")),
        "w_gate": ParamDef((d, di), dt, ("data", "model")),
        "wq": ParamDef((di, di), dt, ("data", "model")),
        "wk": ParamDef((di, di), dt, ("data", "model")),
        "wv": ParamDef((di, di), dt, ("data", "model")),
        "w_if": ParamDef((di, 2 * h), jnp.float32, ("data", None)),
        "b_if": ParamDef((2 * h,), jnp.float32, (None,), "zeros"),
        "w_down": ParamDef((di, d), dt, ("model", "data")),
    }


def mlstm_cache_defs(cfg: ArchConfig, batch: int, policy) -> PyTree:
    h = cfg.n_heads
    dh = _inner(cfg) // h
    bax = policy.batch if batch > 1 else None
    # shard the (dh, dh) matrix memory on its first dh dim — head counts
    # (4) don't divide the model axis, but dh (512) always does
    return {
        "C": ParamDef((batch, h, dh, dh), jnp.float32,
                      (bax, None, "model", None), "zeros"),
        "n": ParamDef((batch, h, dh), jnp.float32, (bax, None, "model"),
                      "zeros"),
    }


def _mlstm_chunk(q, k, v, log_f, i_gate, C0, n0):
    """One chunk of the chunkwise-parallel mLSTM.

    q/k/v: (B, H, c, Dh); log_f, i_gate: (B, H, c); C0: (B, H, Dh, Dh);
    n0: (B, H, Dh). Returns (h, C1, n1).
    """
    b, hh, c, dh = q.shape
    L = jnp.cumsum(log_f, axis=-1)  # (B,H,c) cumulative log decay
    # intra-chunk: D[t,s] = exp(L_t - L_s) * i_s  for s <= t
    diff = L[..., :, None] - L[..., None, :]  # (B,H,c,c)
    tri = jnp.tril(jnp.ones((c, c), bool))
    D = jnp.where(tri, jnp.exp(diff), 0.0) * i_gate[..., None, :]
    scale = 1.0 / jnp.sqrt(dh)
    att = (q @ k.swapaxes(-1, -2)) * scale * D  # (B,H,c,c)
    intra = att @ v  # (B,H,c,Dh)
    # inter-chunk: h_t += exp(L_t) * (q_t C0), with C0 in k (x) v layout
    decay_t = jnp.exp(L)[..., None]  # (B,H,c,1)
    inter = (q @ C0) * scale * decay_t
    num = intra + inter
    # normalizer: q_t . n_t, with n_t = sum_{s<=t} e^{L_t-L_s} i_s k_s
    #             + e^{L_t} n0  ->  row-sum of att + decayed q.n0
    intra_den = jnp.sum(att, axis=-1, keepdims=True)  # (B,H,c,1)
    inter_den = (q @ n0[..., None]) * scale * decay_t  # (B,H,c,1)
    den = jnp.abs(intra_den + inter_den)
    h = num / jnp.maximum(den, 1.0)
    # state update: C1 = exp(L_c) C0 + sum_s exp(L_c - L_s) i_s k_s v_s^T
    w = jnp.exp(L[..., -1:] - L) * i_gate  # (B,H,c)
    C1 = jnp.exp(L[..., -1])[..., None, None] * C0 + jnp.einsum(
        "bhc,bhcd,bhce->bhde", w, k, v
    )
    n1 = jnp.exp(L[..., -1])[..., None] * n0 + jnp.einsum("bhc,bhcd->bhd", w, k)
    return h, C1, n1


def mlstm_apply(
    cfg: ArchConfig,
    p: PyTree,
    x: jax.Array,
    cache: Optional[PyTree] = None,
    decode: bool = False,
    policy=None,
) -> tuple[jax.Array, Optional[PyTree]]:
    """x: (B, S, d) -> (out, new_cache)."""
    b, s, d = x.shape
    h = cfg.n_heads
    di = _inner(cfg)
    dh = di // h
    up = x @ p["w_up"].astype(x.dtype)  # (B,S,di)
    gate = jax.nn.silu(x @ p["w_gate"].astype(x.dtype))

    def heads(m):
        return m.reshape(b, -1, h, dh).swapaxes(1, 2).astype(jnp.float32)

    q = heads(up @ p["wq"].astype(x.dtype))
    k = heads(up @ p["wk"].astype(x.dtype))
    v = heads(up @ p["wv"].astype(x.dtype))
    gates = up.astype(jnp.float32) @ p["w_if"] + p["b_if"]  # (B,S,2H)
    gates = gates.reshape(b, s, 2, h).swapaxes(1, 3)  # (B,H,2,S)
    i_gate = jax.nn.sigmoid(gates[:, :, 0])  # (B,H,S)
    log_f = jax.nn.log_sigmoid(gates[:, :, 1])  # (B,H,S)

    if decode:
        assert cache is not None and s == 1
        f1 = jnp.exp(log_f[..., 0])[..., None, None]
        # k (x) v state layout — must match the chunkwise-parallel form
        C1 = f1 * cache["C"] + (i_gate[..., 0])[..., None, None] * (
            k[:, :, 0, :, None] @ v[:, :, 0, None, :]
        )
        n1 = f1[..., 0] * cache["n"] + i_gate[..., 0][..., None] * k[:, :, 0]
        scale = 1.0 / jnp.sqrt(dh)
        num = jnp.einsum("bhd,bhde->bhe", q[:, :, 0], C1) * scale
        den = jnp.abs(jnp.sum(n1 * q[:, :, 0], -1, keepdims=True)) * scale
        hv = (num / jnp.maximum(den, 1.0))[:, :, None, :]  # (B,H,1,Dh)
        new_cache = {"C": C1, "n": n1}
    else:
        c = min(_CHUNK, s)
        assert s % c == 0, (s, c)
        nch = s // c

        def body(carry, xs):
            C0, n0 = carry
            qc, kc, vc, lfc, igc = xs
            hv, C1, n1 = _mlstm_chunk(qc, kc, vc, lfc, igc, C0, n0)
            return (C1, n1), hv

        def split(m):  # (B,H,S,*) -> (nch, B,H,c,*)
            return m.reshape(b, h, nch, c, *m.shape[3:]).swapaxes(0, 2).swapaxes(1, 2)

        C0 = jnp.zeros((b, h, dh, dh), jnp.float32)
        n0 = jnp.zeros((b, h, dh), jnp.float32)
        if cache is not None:
            C0, n0 = cache["C"], cache["n"]
        elif policy is not None:
            # pin the recurrent carry to the batch sharding — fresh zeros
            # carry no sharding, and GSPMD would replicate the whole scan
            C0 = policy.constrain(C0, (policy.batch, None, None, None))
            n0 = policy.constrain(n0, (policy.batch, None, None))
        (C1, n1), hv = jax.lax.scan(
            body, (C0, n0),
            (split(q), split(k), split(v), split(log_f), split(i_gate)),
        )
        hv = hv.swapaxes(1, 2).swapaxes(0, 2).reshape(b, h, s, dh)
        new_cache = {"C": C1, "n": n1} if cache is not None else None

    merged = hv.swapaxes(1, 2).reshape(b, -1, di).astype(x.dtype)
    out = (gate * merged) @ p["w_down"].astype(x.dtype)
    return out.astype(x.dtype), new_cache


# ---------------------------------------------------------------- sLSTM ------


def slstm_defs(cfg: ArchConfig) -> PyTree:
    d = cfg.d_model
    h = cfg.n_heads
    dh = d // h
    dt = jnp.dtype(cfg.param_dtype)
    return {
        # input -> 4 gates (i, f, z, o), fused
        "w_in": ParamDef((d, 4 * d), dt, ("data", "model")),
        "b_in": ParamDef((4 * d,), jnp.float32, ("model",), "zeros"),
        # recurrent h_{t-1} -> gates, block-diagonal per head (small; head
        # counts (4) don't divide the model axis -> replicated)
        "r": ParamDef((h, dh, 4 * dh), dt, (None, None, None), init_scale=0.5),
        "w_up": ParamDef((d, _slstm_up(d)), dt, ("data", "model")),
        "w_down": ParamDef((_slstm_up(d), d), dt, ("model", "data")),
    }


def _slstm_up(d: int) -> int:
    """xLSTM's 4/3 FF expansion, rounded to a 256 multiple so the dim is
    shardable over any mesh axis (2048 * 4/3 = 2730 -> 2816)."""
    return ((int(d * 4 / 3) + 255) // 256) * 256


def slstm_cache_defs(cfg: ArchConfig, batch: int, policy) -> PyTree:
    d = cfg.d_model
    bax = policy.batch if batch > 1 else None
    ax = (bax, "model")
    return {
        "c": ParamDef((batch, d), jnp.float32, ax, "zeros"),
        "n": ParamDef((batch, d), jnp.float32, ax, "zeros"),
        "h": ParamDef((batch, d), jnp.float32, ax, "zeros"),
    }


def _slstm_cell(p, xg, state):
    """One timestep. xg: (B, 4d) pre-computed input projection."""
    c, n, h = state
    b, d = c.shape
    hh = p["r"].shape[0]
    dh = d // hh
    # recurrent contribution, block-diagonal per head
    rh = jnp.einsum(
        "bhd,hde->bhe", h.reshape(b, hh, dh), p["r"].astype(jnp.float32)
    )  # (B, H, 4*dh); per-head gates contiguous -> reorder to w_in layout
    rh = rh.reshape(b, hh, 4, dh).swapaxes(1, 2).reshape(b, 4 * d)
    g = xg + rh
    i = jnp.exp(jnp.minimum(g[:, 0 * d : 1 * d], 8.0))  # exp input gate, capped
    f = jax.nn.sigmoid(g[:, 1 * d : 2 * d])
    z = jnp.tanh(g[:, 2 * d : 3 * d])
    o = jax.nn.sigmoid(g[:, 3 * d : 4 * d])
    c1 = f * c + i * z
    n1 = f * n + i
    h1 = o * (c1 / jnp.maximum(jnp.abs(n1), 1.0))
    return (c1, n1, h1), h1


def slstm_apply(
    cfg: ArchConfig,
    p: PyTree,
    x: jax.Array,
    cache: Optional[PyTree] = None,
    decode: bool = False,
    policy=None,
) -> tuple[jax.Array, Optional[PyTree]]:
    b, s, d = x.shape
    xg = (x @ p["w_in"].astype(x.dtype)).astype(jnp.float32) + p["b_in"]

    state = (
        jnp.zeros((b, d), jnp.float32),
        jnp.zeros((b, d), jnp.float32),
        jnp.zeros((b, d), jnp.float32),
    )
    if cache is not None:
        state = (cache["c"], cache["n"], cache["h"])
    elif policy is not None:
        # pin the recurrent carry to the batch sharding (see mlstm_apply)
        state = tuple(
            policy.constrain(t, (policy.batch, None)) for t in state
        )
        xg = policy.constrain(xg, (policy.batch, None, None))

    if decode:
        assert s == 1
        state, h = _slstm_cell(p, xg[:, 0], state)
        hs = h[:, None, :]
        new_cache = {"c": state[0], "n": state[1], "h": state[2]}
    else:
        def run_scan(r_w, xg_, state_):
            def body(carry, xg_t):
                return _slstm_cell({"r": r_w}, xg_t, carry)

            st, hs_ = jax.lax.scan(body, state_, xg_.swapaxes(0, 1))
            return st, hs_.swapaxes(0, 1)  # (B,S,d)

        mesh = getattr(policy, "mesh", None) if policy is not None else None
        bax = getattr(policy, "batch", None) if policy is not None else None
        manual = _axes_set(bax)
        if mesh is not None and manual:
            # shard_map over the batch axes: the time scan is sequential,
            # so GSPMD cannot infer shardings for its (fresh-zeros) carry
            # and cotangents — it replicates the WHOLE 4096-step loop over
            # 'model' (measured 118s memory term for xlstm train before
            # this; EXPERIMENTS.md §Perf). Manual batch sharding makes
            # every step chip-local by construction.
            from jax.sharding import PartitionSpec as P

            state, hs = jax.shard_map(
                run_scan,
                mesh=mesh,
                in_specs=(
                    P(),  # recurrent weights: replicated
                    P(bax, None, None),
                    (P(bax, None),) * 3,
                ),
                out_specs=((P(bax, None),) * 3, P(bax, None, None)),
                axis_names=manual,
                check_vma=False,
            )(p["r"], xg, state)
        else:
            state, hs = run_scan(p["r"], xg, state)
        new_cache = (
            {"c": state[0], "n": state[1], "h": state[2]}
            if cache is not None
            else None
        )

    up = jax.nn.gelu(hs.astype(x.dtype) @ p["w_up"].astype(x.dtype))
    out = up @ p["w_down"].astype(x.dtype)
    return out.astype(x.dtype), new_cache
