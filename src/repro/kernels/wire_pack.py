"""Pallas TPU kernels: fused wire-pack encode + unpack/apply decode
(DESIGN.md §15).

``wire.codec`` historically re-scanned every significance-filtered update
on the host: numpy walks the flat leaf once for ``flatnonzero``, again for
``packbits``, again for the quantizing ``astype``, and once more for the
error-feedback residual — four host passes over a tensor the Pallas
significance kernel just produced on device.  This module fuses that
whole encode into ONE device pass per tile:

    mask   = sig != 0                          (significance support)
    bytes  = packbits(mask, 'little')          (bitmap wire mask)
    qvals  = sig.astype(wire_dtype)            (fp16/bf16 quantization)
    nnz    = sum(mask)                         (per-tile, summed on host)
    resid  = f32(sig) - f32(qvals)             (error-feedback residual)

The bit-packing rides the MXU: a (LANES, LANES) constant weight matrix
``W[l, k] = (l // 8 == k) * 2**(l % 8)`` turns ``mask @ W`` into exactly
numpy's ``packbits(bitorder='little')`` — byte ``k`` of a 128-lane row
collects lanes ``8k .. 8k+7``, each weighted by its power of two (byte
values <= 255, exact in f32).  Compaction of the significant values (and
their flat indices, for the sparse scheme) is a fixed-shape
cumsum-scatter epilogue in the same jit: dynamic output shapes don't
exist on TPU, so the kernel emits full-length arrays and the HOST slices
the first ``nnz`` elements — the only bytes that ever leave the device
boundary are final wire bytes.

Decode is the mirror image: ``_unpack_kernel`` broadcasts each packed
byte to its 8 lanes with the transpose trick (``bytes @ E`` where
``E[k, l] = (l // 8 == k)``), shifts out the lane's bit, and the gather +
fused add scatters the received ``(mask, values)`` pair straight into the
target leaf (``wire_unpack_add``) — the accumulate the worker's decode
phase performs per peer, without materializing the intermediate dense
update on the host.

Everything here is bit-identical to the numpy codec by construction
(quantization commutes with compaction; both sides round-to-nearest-even)
and property-tested in ``tests/test_wire_pack.py``.  ``interpret=True``
runs the kernels on CPU (the CI validation mode, auto-selected by
``wire.codec`` off the jax backend).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128  # TPU vector lane width
SUBLANES = 8  # fp32 sublane height
BYTES_PER_ROW = LANES // 8  # packed mask bytes per 128-lane row
DEFAULT_BLOCK_ROWS = 256  # (256, 128) fp32 tile = 128 KiB/operand in VMEM


def pick_block_rows(n: int) -> int:
    """Smallest legal row-block covering an ``n``-element flat leaf:
    full tiles for big leaves, one (8*k, 128) tile for small ones so a
    4 KiB leaf doesn't pad out to 128 KiB."""
    rows = -(-max(n, 1) // LANES)
    return min(DEFAULT_BLOCK_ROWS, -(-rows // SUBLANES) * SUBLANES)


def _pad_to_tiles(flat: jax.Array, block_rows: int) -> tuple[jax.Array, int]:
    n = flat.shape[0]
    tile = block_rows * LANES
    pad = (-n) % tile
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANES), n


def _pack_weights() -> jax.Array:
    """(LANES, LANES) bit-pack matrix: ``W[l, k] = (l//8 == k) * 2**(l%8)``
    — ``mask_f32 @ W`` is numpy's little-endian packbits per row (bytes
    land in lanes 0..15, the rest are zero)."""
    src = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)  # lane l
    dst = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)  # byte k
    return jnp.where(
        src // 8 == dst, jnp.exp2((src % 8).astype(jnp.float32)), 0.0
    )


def _tile_count(mask: jax.Array) -> jax.Array:
    """A tile's significant-element count, broadcast over one
    (SUBLANES, LANES) int32 block: the smallest output block the TPU
    lowering accepts (a (1, 1) block is refused)."""
    return jnp.broadcast_to(
        jnp.sum(mask.astype(jnp.int32)), (SUBLANES, LANES)
    )


_COUNT_BLOCK = pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0))


def _count_shape(n_tiles: int) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((n_tiles * SUBLANES, LANES), jnp.int32)


def _sum_counts(cnt: jax.Array) -> jax.Array:
    """Total of the per-tile counts: element [0, 0] of each count block."""
    return jnp.sum(cnt[::SUBLANES, 0])


def _pack_kernel(x_ref, q_ref, bits_ref, cnt_ref, res_ref):
    """One (block_rows, LANES) tile: quantize, pack mask bits, count,
    and fold the error-feedback residual — one read, four writes."""
    x = x_ref[...]
    mask = x != 0
    q = x.astype(q_ref.dtype)
    q_ref[...] = q
    res_ref[...] = x.astype(jnp.float32) - q.astype(jnp.float32)
    bytes_f = jnp.dot(
        mask.astype(jnp.float32), _pack_weights(),
        preferred_element_type=jnp.float32,
    )
    bits_ref[...] = bytes_f.astype(jnp.int32)
    cnt_ref[...] = _tile_count(mask)


@functools.partial(
    jax.jit, static_argnames=("vdt", "block_rows", "interpret")
)
def wire_pack(
    flat: jax.Array,
    *,
    vdt,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> tuple[jax.Array, ...]:
    """Fused encode of one flat leaf (n >= 1 elements).

    Args:
      flat: 1-D significance-filtered update (zeros are insignificant).
      vdt: wire value dtype (``wire.codec.quant_dtype`` result).
      interpret: run the kernel body on CPU (validation mode).

    Returns ``(mask_bytes, qdense, cvals, cidx, nnz, residual)``:
      mask_bytes: uint8[ceil(n/8)] — little-endian packed significance mask;
      qdense: vdt[n] — the dense-scheme wire values (every element quantized);
      cvals: vdt[n] — significant values compacted to the front (host
        slices ``[:nnz]``);
      cidx: int32[n] — their flat indices, same compaction (sparse scheme);
      nnz: int32 scalar — significant-element count;
      residual: f32[n] — error-feedback quantization residual, zero off
        the support (and everywhere when vdt preserves the leaf dtype).
    """
    n = flat.shape[0]
    x2, _ = _pad_to_tiles(flat, block_rows)
    rows = x2.shape[0]
    grid = (rows // block_rows,)
    block = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    q2, bits2, cnt, res2 = pl.pallas_call(
        _pack_kernel,
        grid=grid,
        in_specs=[block],
        out_specs=[
            block,
            block,
            _COUNT_BLOCK,
            block,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), vdt),
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            _count_shape(grid[0]),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(x2)
    nnz = _sum_counts(cnt)
    mask_bytes = (
        bits2[:, :BYTES_PER_ROW].astype(jnp.uint8).reshape(-1)[: (n + 7) // 8]
    )
    qdense = q2.reshape(-1)[:n]
    res = res2.reshape(-1)[:n]
    # fixed-shape compaction: ascending cumsum positions preserve flat
    # order, the insignificant lanes scatter out of bounds and drop
    mask = x2.reshape(-1)[:n] != 0
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    tgt = jnp.where(mask, pos, n)
    cvals = jnp.zeros((n,), vdt).at[tgt].set(qdense, mode="drop")
    cidx = (
        jnp.zeros((n,), jnp.int32)
        .at[tgt]
        .set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    )
    return mask_bytes, qdense, cvals, cidx, nnz, res


def _nnz_kernel(x_ref, cnt_ref):
    cnt_ref[...] = _tile_count(x_ref[...] != 0)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret")
)
def wire_nnz(
    flat: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jax.Array:
    """Significant-element count of a flat tensor as ONE kernel pass —
    the hit counter the pod collectives' byte accounting rides when the
    fused path is on (same tiling as ``wire_pack``, so the count and the
    packed bytes can never disagree)."""
    x2, _ = _pad_to_tiles(flat, block_rows)
    rows = x2.shape[0]
    grid = (rows // block_rows,)
    cnt = pl.pallas_call(
        _nnz_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=_COUNT_BLOCK,
        out_shape=_count_shape(grid[0]),
        interpret=interpret,
    )(x2)
    return _sum_counts(cnt)


def _unpack_kernel(b_ref, bits_ref):
    """Bytes (lanes 0..15) -> 0/1 mask bits (all 128 lanes) for one tile:
    broadcast byte ``l // 8`` to lane ``l`` via the transpose of the pack
    matrix, then shift out bit ``l % 8``."""
    src = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)  # byte k
    dst = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)  # lane l
    spread = (dst // 8 == src).astype(jnp.float32)
    byte_per_lane = jnp.dot(
        b_ref[...], spread, preferred_element_type=jnp.float32
    ).astype(jnp.int32)
    shift = jax.lax.broadcasted_iota(jnp.int32, byte_per_lane.shape, 1) % 8
    bits_ref[...] = jax.lax.shift_right_logical(byte_per_lane, shift) & 1


def _add_kernel(t_ref, u_ref, o_ref):
    o_ref[...] = t_ref[...] + u_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret")
)
def wire_unpack_add(
    target: jax.Array,
    mask_bytes: jax.Array,
    cvals: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jax.Array:
    """Fused decode/apply: scatter a received ``(mask, values)`` pair
    straight into ``target`` (the parameter leaf or a peer-sum
    accumulator).

    Args:
      target: 1-D accumulation target of the leaf dtype (n elements).
      mask_bytes: uint8[ceil(n/8)] little-endian packed significance mask.
      cvals: wire-dtype significant values, front-packed and padded to a
        static capacity >= nnz (the pad is never gathered: every masked
        lane's cumsum position is < nnz).

    Returns ``target + decoded`` — identical to numpy's
    ``target += decode_leaf(...)`` including the unconditional ``+ 0``
    off the support (so a stray ``-0.0`` in the target normalizes the
    same way on both paths).
    """
    n = target.shape[0]
    # embed the packed bytes at their rows' first 16 lanes, as f32 (the
    # unpack kernel broadcasts them over the MXU; values <= 255, exact)
    b2, _ = _pad_to_tiles(
        jnp.zeros((n,), jnp.float32), block_rows
    )  # row layout template
    rows = b2.shape[0]
    mb = mask_bytes.shape[0]
    bpad = jnp.pad(
        mask_bytes.astype(jnp.float32), (0, rows * BYTES_PER_ROW - mb)
    ).reshape(rows, BYTES_PER_ROW)
    b = jnp.zeros((rows, LANES), jnp.float32).at[:, :BYTES_PER_ROW].set(bpad)
    grid = (rows // block_rows,)
    block = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    bits2 = pl.pallas_call(
        _unpack_kernel,
        grid=grid,
        in_specs=[block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        interpret=interpret,
    )(b)
    mask = bits2.reshape(-1)[:n] == 1
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    cap = cvals.shape[0]
    gathered = cvals[jnp.clip(jnp.where(mask, pos, 0), 0, cap - 1)]
    upd = jnp.where(mask, gathered, jnp.zeros_like(gathered)).astype(
        target.dtype
    )
    t2, _ = _pad_to_tiles(target, block_rows)
    u2, _ = _pad_to_tiles(upd, block_rows)
    out2 = pl.pallas_call(
        _add_kernel,
        grid=grid,
        in_specs=[block, block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), target.dtype),
        interpret=interpret,
    )(t2, u2)
    return out2.reshape(-1)[:n]


@functools.partial(
    jax.jit, static_argnames=("n", "dtype", "block_rows", "interpret")
)
def wire_unpack(
    mask_bytes: jax.Array,
    cvals: jax.Array,
    *,
    n: int,
    dtype,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jax.Array:
    """Decode-only form: the fused scatter into a zero leaf."""
    return wire_unpack_add(
        jnp.zeros((n,), dtype), mask_bytes, cvals,
        block_rows=block_rows, interpret=interpret,
    )
