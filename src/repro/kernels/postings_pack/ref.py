"""Pure-jnp oracle for lane-blocked PFor packing.

Format (TPU adaptation of PForDelta, DESIGN.md §2):
  * the delta stream is grouped into blocks of 128 (the VPU lane width);
  * each block is packed at one bit width bw = bits(max(block));
  * packed layout per block: ``bw`` bit-planes x 4 words of 32 lanes each —
    plane j, word w holds bit j of lanes [32w, 32w+32).

The device kernel emits a fixed worst-case buffer (nb, 32, 4) plus the
per-block bit widths; compaction to ``sum(bw_b) * 16`` bytes happens at
flush (host side), exactly like exception-free PFor on GPUs emits
fixed-stride blocks that a second pass compacts.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

BLOCK = 128
WORDS_PER_PLANE = BLOCK // 32  # 4


def bit_width(block_max: jnp.ndarray) -> jnp.ndarray:
    """ceil(log2(max+1)), with bw(0) = 0 -> store nothing for all-zero."""
    return (32 - lax.clz(block_max.astype(jnp.uint32))).astype(jnp.int32)


def pack_ref(deltas: jnp.ndarray):
    """deltas: (nb, 128) uint32 -> (packed (nb, 32, 4) uint32, bw (nb,) int32).

    Planes >= bw are zero (masked), so the compacted stream is
    ``packed[b, :bw[b], :]``.
    """
    assert deltas.shape[-1] == BLOCK, deltas.shape
    d = deltas.astype(jnp.uint32)
    nb = d.shape[0]
    bw = bit_width(d.max(axis=-1))
    planes = jnp.arange(32, dtype=jnp.uint32)
    bits = (d[:, None, :] >> planes[None, :, None]) & jnp.uint32(1)  # (nb,32,128)
    lanes = bits.reshape(nb, 32, WORDS_PER_PLANE, 32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    words = (lanes * weights[None, None, None, :]).sum(axis=-1, dtype=jnp.uint32)
    mask = planes[None, :, None] < bw[:, None, None].astype(jnp.uint32)
    return jnp.where(mask, words, jnp.uint32(0)), bw


def unpack_ref(packed: jnp.ndarray, bw: jnp.ndarray):
    """(nb, 32, 4) uint32 + (nb,) -> (nb, 128) uint32."""
    nb = packed.shape[0]
    lane = jnp.arange(BLOCK)
    word_idx, bit_idx = lane // 32, (lane % 32).astype(jnp.uint32)
    w = packed[:, :, word_idx]  # (nb, 32, 128)
    bits = (w >> bit_idx[None, None, :]) & jnp.uint32(1)
    planes = jnp.arange(32, dtype=jnp.uint32)
    valid = planes[None, :, None] < bw[:, None, None].astype(jnp.uint32)
    vals = jnp.where(valid, bits, jnp.uint32(0)) << planes[None, :, None]
    return vals.sum(axis=1, dtype=jnp.uint32)


def _xp(x):
    """numpy for host arrays (the storage codec), jax.numpy otherwise."""
    return np if isinstance(x, np.ndarray) else jnp


def _bit_transpose32(x):
    """(..., 32) uint32 bit-matrix transpose: out[..., t] bit j ==
    x[..., j] bit t. Hacker's Delight transpose32, vectorized over the
    leading axes — 5 mask/shift stages instead of materializing the
    (..., 32, 32) bit tensor."""
    xp = _xp(x)
    j = 16
    m = np.uint32(0x0000FFFF)
    while j:
        xs = x.reshape(x.shape[:-1] + (x.shape[-1] // (2 * j), 2, j))
        hi, lo = xs[..., 0, :], xs[..., 1, :]
        t = ((hi >> np.uint32(j)) ^ lo) & m
        hi, lo = hi ^ (t << np.uint32(j)), lo ^ t
        x = xp.stack([hi, lo], axis=-2).reshape(x.shape)
        j >>= 1
        m = m ^ (m << np.uint32(j))
    return x


def pack_fast(deltas: np.ndarray):
    """Host-side exact equivalent of ``pack_ref`` (asserted in tests) via
    bit-plane transpose, numpy in and out: the storage codec's pack. No
    bw masking is needed: bw = bits(block max), so every value's planes
    >= bw are zero already."""
    assert deltas.shape[-1] == BLOCK, deltas.shape
    d = np.asarray(deltas, np.uint32)
    nb = d.shape[0]
    bw = np.frexp(d.max(axis=-1).astype(np.float64))[1].astype(np.int32)
    lanes = d.reshape(nb, WORDS_PER_PLANE, 32)     # [w, t] = lane 32w+t
    planes = _bit_transpose32(lanes)               # [w, j]: bit t = lane bit j
    return np.swapaxes(planes, -2, -1), bw         # (nb, 32, 4)


def unpack_fast(packed, bw):
    """Exact-equivalent of ``unpack_ref`` (asserted in tests) via bit-plane
    transpose — the hot read-path unpack. Requires planes >= bw to be zero,
    which ``pack_ref``/the device kernel guarantee, so ``bw`` is not needed
    to mask (kept for signature parity). numpy in, numpy out; jax
    otherwise."""
    del bw
    nb = packed.shape[0]
    planes_last = _xp(packed).swapaxes(packed, -2, -1)  # (nb, 4, 32)
    vals = _bit_transpose32(planes_last)            # lane 32w+t at [, w, t]
    return vals.reshape(nb, BLOCK)


def compact_planes(packed: np.ndarray, bw: np.ndarray) -> np.ndarray:
    """Host-side compaction of a fixed-stride packed buffer: keep only the
    ``bw[b]`` live planes of each block. (nb, 32, 4) (or the device
    layout's (nb, 128)) uint32 + (nb,) -> (sum(bw), 4) uint32 rows,
    block-major then plane-major — the byte stream the storage codec
    writes at flush (the docstring's 'compaction to sum(bw_b) * 16 bytes
    happens at flush (host side)')."""
    packed = np.asarray(packed, np.uint32).reshape(-1, 32, WORDS_PER_PLANE)
    bw = np.asarray(bw, np.int64)
    mask = np.arange(32)[None, :] < bw[:, None]
    return packed[mask]


def compact_words(packed: np.ndarray, bw: np.ndarray):
    """The COMPACT index layout of a packed buffer: ``(words, coff)``.
    ``words`` (M, 128) uint32 holds the compacted plane rows as one flat
    word stream cut into 128-word rows, zero-padded so that the two rows
    starting at any block's first word are in bounds (a 32-plane block
    spans at most two); ``coff`` (nb,) int32 is each block's first plane
    row, so its words start at flat offset ``4 * coff``."""
    flat = compact_planes(packed, bw).reshape(-1)
    words = np.zeros((flat.size // BLOCK + 2) * BLOCK, np.uint32)
    words[:flat.size] = flat
    bw = np.asarray(bw, np.int64)
    return words.reshape(-1, BLOCK), (np.cumsum(bw) - bw).astype(np.int32)


def expand_planes(rows: np.ndarray, bw: np.ndarray) -> np.ndarray:
    """Inverse of ``compact_planes``: scatter the compacted (sum(bw), 4)
    rows back into the fixed-stride (nb, 32, 4) buffer ``unpack_fast``
    consumes; dead planes (>= bw) are zero, as the pack contract requires."""
    rows = np.asarray(rows, np.uint32).reshape(-1, WORDS_PER_PLANE)
    bw = np.asarray(bw, np.int64)
    full = np.zeros((len(bw), 32, WORDS_PER_PLANE), np.uint32)
    mask = np.arange(32)[None, :] < bw[:, None]
    if rows.shape[0] != int(mask.sum()):
        raise ValueError(f"compacted stream holds {rows.shape[0]} plane rows"
                         f", bit widths require {int(mask.sum())}")
    full[mask] = rows
    return full


def packed_bytes(bw: jnp.ndarray) -> jnp.ndarray:
    """Compacted size in bytes: bw planes x 4 words x 4 bytes + 1 byte/block
    header (the bit width). float accumulation: counts can exceed int32."""
    return (bw.astype(jnp.float32) * 16 + 1).sum()
