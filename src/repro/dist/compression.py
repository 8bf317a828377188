"""Compressed ISP collectives over the pod axis (DESIGN.md §2).

This module is the error-feedback form of the MLLess significance filter:
parameters are shared across the data-parallel pod axis, every pod keeps a
private residual, and only the *significant* part of ``residual + update``
crosses the wire. The exchange is pure data-flow — a leading tensor dim of
size ``n_pods`` stands in for the pod collective, so the same function runs
under ``vmap`` on one chip, under GSPMD on a real multi-pod mesh, and in
unit tests with ``n_pods == 1`` (where Corollary 1 makes it BSP-exact at
v = 0).

Exchange schemes (``CompressionConfig.scheme``):

* ``dense``  — the filtered update is exchanged as a full dense tensor
  (all-reduce over 'pod'). Exact filter semantics, no wire saving — the
  paper's observation that arbitrary-sparsity updates don't compress a
  dense collective. This is the correctness baseline.
* ``topk``   — per pod, per ``block``-sized block, keep the ``budget``
  fraction of entries with the largest magnitude; everything else returns
  to the residual (error feedback — no update mass is ever lost). The
  kept entries are combined as the other schemes are, by a dense float32
  sum over the pod dim: on a mesh an all-reduce, with no wire saving yet.
* ``bitmap`` — exchange the significant entries as (bitmask, packed
  values): numerically identical to ``dense`` (the same entries move),
  only the wire encoding differs — the paper's Redis sparse encoding,
  collective form.

Byte accounting is NOT hand-rolled here: each scheme maps to a
``repro.wire`` codec (dense→dense, topk→sparse, bitmap→bitmap; override
with ``CompressionConfig.wire``) and the per-step ``wire_bytes`` stat is
computed from ``repro.wire.codec.leaf_nbytes`` — the same formula the
live FaaS runtime's encoder asserts against, so the bytes this module
reports to the simulator/auto-tuner equal the bytes the runtime would
measure, by construction (DESIGN.md §10).

The significance split is ``core.isp.significance_split``, which XLA fuses
with the optimizer update on the train path. The Pallas kernel
``kernels.significance`` takes its place only under
``CompressionConfig.fused``, which no trainer mode sets.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.isp import significance_split
from repro.kernels import wire_pack
from repro.kernels.significance import significance_filter
from repro.wire import codec as wire_codec

PyTree = Any

_SCHEMES = ("dense", "topk", "bitmap")
# exchange scheme -> default repro.wire encoding of what crosses the pod axis
_WIRE_OF = {"dense": "dense", "topk": "sparse", "bitmap": "bitmap"}


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """Static exchange configuration (hashable: closed over by jit).

    Attributes:
      scheme: exchange scheme — 'dense', 'topk', or 'bitmap' (module doc).
      budget: topk only — fraction of entries kept per block (0 < b <= 1).
      block: topk only — block size for the block-local top-k (TPU-friendly
        multiples of 128; the compaction granularity of the exchange).
      wire: ``repro.wire`` codec the byte accounting charges for
        ('dense'|'sparse'|'bitmap'); None derives it from ``scheme``.
      fused: route the significance split through the Pallas kernel
        (``kernels.significance``) instead of the jnp reference.
      interpret: run the Pallas kernel in interpret mode (CPU validation).
    """

    scheme: str = "dense"
    budget: float = 0.01
    block: int = 128
    wire: Optional[str] = None
    fused: bool = False
    interpret: bool = False

    def __post_init__(self):
        if self.scheme not in _SCHEMES:
            raise ValueError(
                f"scheme must be one of {_SCHEMES}, got {self.scheme!r}"
            )
        if self.wire is not None and self.wire not in wire_codec.SCHEMES:
            raise ValueError(
                f"wire must be one of {wire_codec.SCHEMES}, got {self.wire!r}"
            )
        if not 0.0 < self.budget <= 1.0:
            raise ValueError(f"budget must be in (0, 1], got {self.budget}")
        if self.block < 1:
            raise ValueError(f"block must be >= 1, got {self.block}")

    @property
    def wire_scheme(self) -> str:
        """The ``repro.wire`` codec this exchange is accounted as."""
        return self.wire or _WIRE_OF[self.scheme]

    def k_per_block(self, block: Optional[int] = None) -> int:
        """Entries kept per block under the topk budget (always >= 1)."""
        b = self.block if block is None else block
        return max(1, min(b, int(round(b * self.budget))))


def split_significant(
    u: jax.Array,
    x: jax.Array,
    r: jax.Array,
    v_t: jax.Array,
    *,
    floor: float = 1e-8,
    fused: bool = False,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(sig, res) with sig + res == r + u; |acc| > v_t * max(|x|, floor).

    ``x`` may have fewer leading dims than ``u``/``r`` (shared params vs a
    pod-stacked update): it is broadcast. The fused path flattens the whole
    (pod-stacked) tensor into one Pallas grid, so the pod dim rides the
    same kernel launch.
    """
    x_b = jnp.broadcast_to(x, u.shape)
    if fused:
        return significance_filter(
            u, x_b, r, jnp.asarray(v_t, jnp.float32), floor=floor,
            interpret=interpret,
        )
    sig, res, _ = significance_split(r + u, x_b, v_t, floor)
    return sig, res


# Rows of the TPU's (8, 128) memory tile. A leaf's blocks taken as
# (_TILE_ROWS, block) tiles of its last two axes form a view that XLA lays
# out as the leaf itself, so the block argmax reads the leaf where it lies;
# the plain (..., last // block, block) view is relaid out per leaf first.
_TILE_ROWS = 8


def _block_topk_mask(sig: jax.Array, cfg: CompressionConfig) -> jax.Array:
    """Boolean keep-mask of the per-block top-k |entries| of one pod slice.

    A block is ``block`` consecutive entries of the row-major flatten, and
    each block is selected on its own, so the order in which they are laid
    out does not matter. ``k == 1`` keeps the block's argmax: one fused
    reduction, where ``lax.top_k`` lowers to a full sort; both keep the
    lowest index among equal magnitudes, so the masks are equal bit for bit.
    It reads the blocks in place when the last axis is a multiple of
    ``block`` and the one before it of ``_TILE_ROWS``. Otherwise, and for
    ``k > 1``, the slice is flattened to (nb, block) with zero padding, and
    padded entries are dropped from the mask (0 moves 0 mass either way).
    Returns a mask of ``sig.shape``.
    """
    n = sig.size
    block = min(cfg.block, max(n, 1))
    k = cfg.k_per_block(block)
    if (k == 1 and sig.ndim >= 2 and sig.shape[-2] % _TILE_ROWS == 0
            and sig.shape[-1] % block == 0):
        *lead, rows, last = sig.shape
        tiles = sig.reshape(
            *lead, rows // _TILE_ROWS, _TILE_ROWS, last // block, block
        ).swapaxes(-3, -2)
        keep = _argmax_mask(tiles)
        return keep.swapaxes(-3, -2).reshape(sig.shape)
    flat = sig.reshape(-1)
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    if k == 1:
        keep = _argmax_mask(blocks)
    else:
        _, idx = jax.lax.top_k(jnp.abs(blocks), k)  # (nb, k)
        keep = jnp.zeros_like(blocks, dtype=jnp.bool_)
        keep = jnp.put_along_axis(keep, idx, True, axis=-1, inplace=False)
    # padded entries are never real mass; drop them from the mask
    if pad:
        valid = (jnp.arange(flat.shape[0]) < n).reshape(-1, block)
        keep = keep & valid
    return keep.reshape(-1)[:n].reshape(sig.shape)


def _argmax_mask(blocks: jax.Array) -> jax.Array:
    """True at the first largest |entry| of each last-axis block."""
    idx = jnp.argmax(jnp.abs(blocks), axis=-1)
    return jnp.arange(blocks.shape[-1]) == idx[..., None]


def isp_compressed_step(
    cfg: CompressionConfig,
    updates_pod: PyTree,
    params: PyTree,
    residual_pod: PyTree,
    v_t: jax.Array,
    *,
    floor: float = 1e-8,
) -> tuple[PyTree, PyTree, dict[str, jax.Array]]:
    """One error-feedback ISP exchange over the leading pod axis.

    Args:
      cfg: wire encoding configuration.
      updates_pod: per-pod local updates u_p, every leaf shaped (P, *s).
      params: shared parameters x (no pod axis) — the significance
        denominator AND the broadcast target.
      residual_pod: per-pod carried residuals r_p, leaves (P, *s).
      v_t: scalar significance threshold (v = 0 reduces to BSP exactly).
      floor: absolute-magnitude floor for |x| ~ 0 denominators.

    Returns:
      ``(combined, new_residual_pod, stats)`` where ``combined`` has the
      shape of ``params`` (the summed communicated mass to apply), and the
      invariant ``sent_p + new_residual_p == residual_p + update_p`` holds
      per pod for every leaf — error feedback conserves update mass under
      every scheme. ``stats`` carries ``sent_fraction`` (communicated
      entries / total entries) and ``wire_bytes`` under
      ``cfg.wire_scheme``'s ``repro.wire`` encoding — per pod, per leaf,
      the exact bytes the live runtime's encoder would produce.
    """
    treedef = jax.tree.structure(params)
    u_leaves = treedef.flatten_up_to(updates_pod)
    x_leaves = jax.tree.leaves(params)
    r_leaves = treedef.flatten_up_to(residual_pod)

    wire_scheme = cfg.wire_scheme
    combined, new_res = [], []
    n_sent = jnp.asarray(0.0, jnp.float32)
    n_total = 0
    wire = jnp.asarray(0.0, jnp.float32)
    for u, x, r in zip(u_leaves, x_leaves, r_leaves):
        sig, res = split_significant(
            u, x, r, v_t, floor=floor, fused=cfg.fused,
            interpret=cfg.interpret,
        )
        if cfg.scheme == "topk":
            keep = jax.vmap(lambda s: _block_topk_mask(s, cfg))(sig)
            sent = jnp.where(keep, sig, jnp.zeros_like(sig))
            res = res + (sig - sent)  # unsent significant mass feeds back
        else:
            sent = sig
        combined.append(jnp.sum(sent.astype(jnp.float32), axis=0)
                        .astype(x.dtype))
        new_res.append(res)
        if cfg.fused and sent.size > 0:
            # same count, via the pack kernel's tiled reduction — keeps the
            # whole hit-accounting path on the fused kernels when they are
            # selected (kernels/wire_pack.py, bit-identical to the jnp sum)
            hits = wire_pack.wire_nnz(
                sent.reshape(-1), interpret=cfg.interpret
            ).astype(jnp.float32)
        else:
            hits = jnp.sum((sent != 0).astype(jnp.float32))
        n_sent = n_sent + hits
        n_total += sent.size
        # shared-codec accounting (works on traced scalars): each pod ships
        # one encoded leaf, so the step costs P * fixed-part (dense bytes /
        # bitmap mask) plus the marginal per-entry bytes times total hits
        n_pods, leaf_n = sent.shape[0], int(sent.size // sent.shape[0])
        itemsize = x.dtype.itemsize
        fixed = wire_codec.leaf_nbytes(wire_scheme, leaf_n, 0, itemsize)
        marginal = (
            wire_codec.leaf_nbytes(wire_scheme, leaf_n, 1, itemsize) - fixed
        )
        wire = wire + jnp.asarray(
            float(n_pods * fixed), jnp.float32
        ) + hits * float(marginal)

    stats = {
        "sent_fraction": n_sent / jnp.maximum(float(n_total), 1.0),
        "wire_bytes": wire,
    }
    return (
        treedef.unflatten(combined),
        treedef.unflatten(new_res),
        stats,
    )


def apply_combined(params: PyTree, combined: PyTree) -> PyTree:
    """x' = x + sum_p sent_p in fp32, cast back to each leaf's dtype."""
    return jax.tree.map(
        lambda p, c: (
            p.astype(jnp.float32) + c.astype(jnp.float32)
        ).astype(p.dtype),
        params, combined,
    )
