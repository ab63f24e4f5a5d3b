"""Pallas TPU kernel: lane-blocked PFor pack/unpack.

TPU adaptation (DESIGN.md §2): VByte-style byte-aligned codecs are branchy
and warp-shaped; on the TPU VPU we instead pack 128-delta blocks (one block
per vector lane row) with per-block bit width, using only vector shifts,
ands, selects and 32-lane reductions — no MXU, no gather. Tiles of
``block_rows`` blocks are staged through VMEM via BlockSpec.

Device layout: a block's 32 planes x 4 words are one 128-lane row, lane
``4 * plane + word`` — the row-major flattening of the (32, 4) form the
reference and the storage codec use, so ``reshape(nb, 32, 4)`` converts
between them. A (nb, 32, 4) array would be lane-padded 32x in HBM.

Mosaic has no unsigned reductions or unsigned-to-float casts, so the
kernels see the words as int32 bit patterns: the bit planes of a value
are disjoint, so an int32 sum of them (wrapping at bit 31) is exact.

Validated against ref.py in interpret mode (tests/test_kernels.py) and
compiled for v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.postings_pack import ref

BLOCK = 128
DEFAULT_BLOCK_ROWS = 256  # deltas tile: 256 x 128 x 4B = 128 KiB in VMEM


def grid_rows(nb: int, block_rows: int) -> tuple[int, int]:
    """(rows per grid step, padded row count) for ``nb`` blocks: a step
    of ``block_rows`` rows, or the whole array when it is shorter; the
    row count is padded up to a whole number of steps."""
    br = min(block_rows, nb)
    return br, -(-nb // br) * br


def as_i32(x):
    """uint32 bit patterns -> int32 (same bits)."""
    if x.dtype == jnp.uint32:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    return x.astype(jnp.int32)


def pad_rows(x, n: int):
    """Zero-pad the leading axis of ``x`` to ``n`` rows."""
    if x.shape[0] == n:
        return x
    return jnp.pad(x, ((0, n - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def unpack_words(w, bw):
    """(R, 128) int32 packed words (lane 4p+j = word j of plane p) and
    (R, 1) int32 bit widths -> (R, 128) int32 values. Lane l takes bit
    l%32 of word l//32 of every plane; planes at or above ``bw`` are
    masked off, so stray words there (the compact kernel's window reads
    the next block's planes) never contribute."""
    R = w.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, BLOCK), 1)
    word, bit = lane // 32, lane % 32
    acc = jnp.zeros((R, BLOCK), jnp.int32)
    for p in range(32):
        sel = w[:, 4 * p + 3:4 * p + 4]
        for j in (2, 1, 0):
            sel = jnp.where(word == j, w[:, 4 * p + j:4 * p + j + 1], sel)
        acc = acc | ((jax.lax.shift_right_logical(sel, bit) & 1) << p)
    mask = jnp.where(bw >= 32, -1, (1 << jnp.minimum(bw, 31)) - 1)
    return acc & mask


def _pack_kernel(deltas_ref, packed_ref):
    d = deltas_ref[...]  # (R, 128) int32 bit patterns
    R = d.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, BLOCK), 1)
    word, bit = lane // 32, lane % 32

    def plane(p, out):  # a loop, not an unroll: keeps VMEM live ranges short
        b = (jax.lax.shift_right_logical(d, p) & 1) << bit
        for j in range(4):
            wj = jnp.sum(jnp.where(word == j, b, 0), axis=1, keepdims=True)
            out = jnp.where(lane == 4 * p + j, wj, out)
        return out

    packed_ref[...] = jax.lax.fori_loop(0, 32, plane,
                                        jnp.zeros((R, BLOCK), jnp.int32))


def _unpack_kernel(packed_ref, bw_ref, deltas_ref):
    deltas_ref[...] = unpack_words(packed_ref[...], bw_ref[...])


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def pack_pallas(deltas, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool):
    """deltas: (nb, 128) uint32, any nb -> (packed (nb, 128) uint32 in the
    device layout, bw (nb,) int32). Planes at or above a block's bit
    width are zero by construction, as ``ref.pack_ref`` masks them."""
    nb = deltas.shape[0]
    br, n = grid_rows(nb, block_rows)
    words = pl.pallas_call(
        _pack_kernel,
        grid=(n // br,),
        in_specs=[pl.BlockSpec((br, BLOCK), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, BLOCK), jnp.int32),
        interpret=interpret,
    )(pad_rows(as_i32(deltas), n))
    bw = ref.bit_width(deltas.astype(jnp.uint32).max(axis=-1))
    return jax.lax.bitcast_convert_type(words[:nb], jnp.uint32), bw


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def unpack_pallas(packed, bw, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                  interpret: bool):
    """packed: (nb, 128) or (nb, 32, 4) uint32, bw: (nb,) -> (nb, 128)
    uint32 values; any nb."""
    nb = packed.shape[0]
    br, n = grid_rows(nb, block_rows)
    out = pl.pallas_call(
        _unpack_kernel,
        grid=(n // br,),
        in_specs=[pl.BlockSpec((br, BLOCK), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, BLOCK), jnp.int32),
        interpret=interpret,
    )(pad_rows(as_i32(packed.reshape(nb, BLOCK)), n),
      pad_rows(bw.astype(jnp.int32).reshape(nb, 1), n))
    return jax.lax.bitcast_convert_type(out[:nb], jnp.uint32)
