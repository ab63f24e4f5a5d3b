"""Pallas TPU skip kernel: fused unpack + prefix-sum + BM25 scoring over
COMPACTED surviving blocks.

The TPU-idiomatic equivalent of block-max WAND's posting cursor (DESIGN.md
§2), after the pruning pass has already *compacted* the survivors: the
grid iterates over the dense survivor array the host gathered
(``core/query.py::compact_survivors``), so the kernel touches exactly the
blocks the MaxScore test kept — cost is proportional to survivors, never
to candidates. (The same kernel also serves the dense oracle, whose
"survivor array" is simply the full candidate grid with a mask.)

Per grid step (``block_rows`` postings blocks, 128 lanes each), all VPU
work:

  * bit-plane unpack of the lane-blocked PFor doc deltas and tfs
    (``postings_pack.kernel.unpack_words``: lane-indexed selects and
    shifts over each block's 128-word row);
  * a log-step inclusive prefix sum across the 128 lanes rebuilding
    absolute doc ids from the block's first doc;
  * the fused BM25 numerator idf * (k1+1) * tf.

Per-block scalars travel as (n, 1) columns, so every block is a legal
(multiple-of-8, full) tile; any block count is zero-padded to whole grid
steps (padding is inactive) and the outputs are sliced back.

The per-doc length norm needs a doc-indexed gather and so stays outside
the kernel (the caller finishes ``score += num / (tf + doc_norm[doc])``).
``ref.py`` is the pure-jnp oracle; parity is asserted in interpret mode
on CPU (tests/test_kernels.py), the v5e compile in
tests/test_tpu_compile.py, and the dispatcher (``ops.py``) runs the
compiled kernel only on TPU.

``bm25_blocks_compact_pallas`` is the fused DECOMPRESS-and-score
variant: the index keeps only the compacted bit-plane words
(``postings_pack.ref.compact_words``: the storage codec's bytes, cut
into 128-word rows), which stay in HBM. Each grid step DMAs every
block's two-row window (a block's at most 128 words start anywhere in
it) into VMEM, rotates the block's words to lane 0, and runs the shared
unpack/score body; words past a block's width belong to the next block
and are masked off by ``unpack_words``. The fixed-stride decoded form
never materializes in HBM — compressed rows in, scores out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.postings_pack.kernel import (as_i32, grid_rows, pad_rows,
                                                unpack_words)

BLOCK = 128
DEFAULT_BLOCK_ROWS = 128


def _col(x, n: int, dtype=jnp.int32):
    """(nb,) per-block vector -> zero-padded (n, 1) column."""
    return pad_rows(x.astype(dtype).reshape(-1, 1), n)


def _words(x, n: int):
    """(nb, 128) or (nb, 32, 4) packed uint32 -> zero-padded (n, 128)."""
    return pad_rows(as_i32(x.reshape(x.shape[0], BLOCK)), n)


def _decode(pd, bwd, first, pt, bwt, idf, *, k1):
    """Unpack + prefix-sum + numerator for one grid step's blocks:
    (R, 128) words and (R, 1) columns -> (docids, tf, num), (R, 128)."""
    acc = unpack_words(pd, bwd)
    shift = 1
    while shift < BLOCK:  # inclusive prefix sum over the 128 lanes
        acc = acc + jnp.pad(acc, ((0, 0), (shift, 0)))[:, :BLOCK]
        shift *= 2
    tf = unpack_words(pt, bwt).astype(jnp.float32)
    return first + acc, tf, idf * (k1 + 1.0) * tf


def _emit(keep, docids, tf, num, doc_ref, tf_ref, num_ref):
    doc_ref[...] = jnp.where(keep, docids, 0)
    tf_ref[...] = jnp.where(keep, tf, 0.0)
    num_ref[...] = jnp.where(keep, num, 0.0)


def _bm25_kernel(pd_ref, bwd_ref, first_ref, pt_ref, bwt_ref, idf_ref,
                 act_ref, doc_ref, tf_ref, num_ref, *, k1):
    docids, tf, num = _decode(pd_ref[...], bwd_ref[...], first_ref[...],
                              pt_ref[...], bwt_ref[...], idf_ref[...], k1=k1)
    _emit(act_ref[...] > 0, docids, tf, num, doc_ref, tf_ref, num_ref)


def _bm25_kernel_midgrid(pd_ref, bwd_ref, first_ref, pt_ref, bwt_ref,
                         idf_ref, act_ref, row_ref, ubf_ref, theta_ref,
                         nmax_ref, doc_ref, tf_ref, num_ref, skip_ref,
                         el_ref, *, k1, k):
    """Midgrid theta tightening: the running carry is no longer a
    diagnostic — it GATES work. ``el_ref`` (1, 128) holds a per-ROW
    running lower bound on the row's final k-th score (lane j = row j,
    seeded from the caller's theta); at each sequential grid step an
    active block whose stored full-score UB falls strictly below its
    row's bound is skipped (outputs zeroed, flag raised), then the KEPT
    blocks' k-th largest pessimistic lane partial num / (tf + norm_max)
    is folded back into the carry by row. Within one step, decisions see
    only earlier steps' updates. ``ref.py::bm25_blocks_midgrid_ref`` is
    the bit-exact oracle."""
    R = pd_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        el_ref[...] = theta_ref[...]

    eq = row_ref[...] == jax.lax.broadcasted_iota(jnp.int32, (R, BLOCK), 1)
    el = el_ref[...]                                                # (1, 128)
    l_row = jnp.sum(jnp.where(eq, el, 0.0), axis=1, keepdims=True)  # (R, 1)
    act = act_ref[...] > 0
    skip = act & (ubf_ref[...] < l_row)
    keep = act & ~skip
    docids, tf, num = _decode(pd_ref[...], bwd_ref[...], first_ref[...],
                              pt_ref[...], bwt_ref[...], idf_ref[...], k1=k1)
    _emit(keep, docids, tf, num, doc_ref, tf_ref, num_ref)
    skip_ref[...] = skip.astype(jnp.int32)
    # fold the kept blocks' k-th-best witnesses into the carry: per block
    # k-1 rounds of (max, retire ties), floored at 0 — every positive
    # lane is a distinct doc, so the result is witnessed by k docs
    cur = jnp.where(keep & (tf > 0), num / (tf + nmax_ref[...]), 0.0)
    for _ in range(max(k - 1, 0)):
        cur = jnp.where(cur == cur.max(axis=1, keepdims=True), -1.0, cur)
    kth = jnp.maximum(cur.max(axis=1, keepdims=True), 0.0)          # (R, 1)
    el_ref[...] = jnp.maximum(el, jnp.where(eq, kth, 0.0
                                            ).max(axis=0, keepdims=True))


def _gather_windows(coff_ref, base, R, words_hbm, win, al, sem):
    """DMA each of this step's R blocks' two-row word windows from HBM
    (all in flight at once), then rotate each block's words to lane 0:
    ``al[i]`` becomes block i's 128 words in the fixed-stride layout."""
    def start(i, c):
        row = (coff_ref[base + i] * 4) // BLOCK
        pltpu.make_async_copy(words_hbm.at[pl.ds(row, 2)], win.at[i],
                              sem.at[0]).start()
        return c

    def wait(i, c):
        pltpu.make_async_copy(words_hbm.at[pl.ds(0, 2)], win.at[i],
                              sem.at[0]).wait()
        return c

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, BLOCK), 1)

    def realign(i, c):
        s = (coff_ref[base + i] * 4) % BLOCK
        w = win[i]
        rot = (BLOCK - s) % BLOCK
        al[pl.ds(i, 1), :] = jnp.where(lane < BLOCK - s,
                                       pltpu.roll(w[0:1, :], rot, 1),
                                       pltpu.roll(w[1:2, :], rot, 1))
        return c

    jax.lax.fori_loop(0, R, start, 0)
    jax.lax.fori_loop(0, R, wait, 0)
    jax.lax.fori_loop(0, R, realign, 0)


def _bm25_compact_kernel(coffd_ref, cofft_ref, wd_hbm, wt_hbm, bwd_ref,
                         first_ref, bwt_ref, idf_ref, act_ref, doc_ref,
                         tf_ref, num_ref, win, al_d, al_t, sem, *, k1):
    """Fused decompress-and-score grid step: fetch this step's blocks
    from the compressed words in HBM, then the shared decode body."""
    R = doc_ref.shape[0]
    base = pl.program_id(0) * R
    _gather_windows(coffd_ref, base, R, wd_hbm, win, al_d, sem)
    _gather_windows(cofft_ref, base, R, wt_hbm, win, al_t, sem)
    docids, tf, num = _decode(al_d[...], bwd_ref[...], first_ref[...],
                              al_t[...], bwt_ref[...], idf_ref[...], k1=k1)
    _emit(act_ref[...] > 0, docids, tf, num, doc_ref, tf_ref, num_ref)


def _lane_out(n):
    return [jax.ShapeDtypeStruct((n, BLOCK), jnp.int32),
            jax.ShapeDtypeStruct((n, BLOCK), jnp.float32),
            jax.ShapeDtypeStruct((n, BLOCK), jnp.float32)]


@functools.partial(jax.jit,
                   static_argnames=("k1", "block_rows", "interpret"))
def bm25_blocks_compact_pallas(cwords_docs, coff_docs, bw_docs, first_doc,
                               cwords_tf, coff_tf, bw_tf, idf, active, *,
                               k1: float = 0.9,
                               block_rows: int = DEFAULT_BLOCK_ROWS,
                               interpret: bool):
    """-> (docids, tf, num) each (S, 128), S the selected block count,
    decoding the selected blocks from the COMPACT word rows inside the
    grid. ``cwords_docs``/``cwords_tf`` are the whole index's (M, 128)
    compact words (``postings_pack.ref.compact_words``), left in HBM;
    ``coff_*``/``bw_*``/``first_doc``/``idf``/``active`` are (S,)
    per-selected-block vectors."""
    nb = coff_docs.shape[0]
    br, n = grid_rows(nb, block_rows)
    col = pl.BlockSpec((br, 1), lambda i, *_: (i, 0))
    lanes = pl.BlockSpec((br, BLOCK), lambda i, *_: (i, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_bm25_compact_kernel, k1=k1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // br,),
            in_specs=[hbm, hbm, col, col, col, col, col],
            out_specs=[lanes, lanes, lanes],
            scratch_shapes=[pltpu.VMEM((br, 2, BLOCK), jnp.int32),
                            pltpu.VMEM((br, BLOCK), jnp.int32),
                            pltpu.VMEM((br, BLOCK), jnp.int32),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=_lane_out(n),
        interpret=interpret,
    )(pad_rows(coff_docs.astype(jnp.int32), n),
      pad_rows(coff_tf.astype(jnp.int32), n),
      as_i32(cwords_docs), as_i32(cwords_tf),
      _col(bw_docs, n), _col(first_doc, n), _col(bw_tf, n),
      _col(idf, n, jnp.float32), _col(active, n))
    return [o[:nb] for o in out]


@functools.partial(jax.jit,
                   static_argnames=("k1", "block_rows", "interpret"))
def bm25_blocks_pallas(packed_docs, bw_docs, first_doc, packed_tf, bw_tf,
                       idf, active, *, k1: float = 0.9,
                       block_rows: int = DEFAULT_BLOCK_ROWS,
                       interpret: bool):
    """-> (docids, tf, num) each (NB, 128). NB is any block count: the
    compacted survivor bucket on the pruned path, the whole candidate
    grid on the exhaustive one. Packed blocks are (NB, 128) words or
    their (NB, 32, 4) form."""
    nb = packed_docs.shape[0]
    br, n = grid_rows(nb, block_rows)
    col = pl.BlockSpec((br, 1), lambda i: (i, 0))
    lanes = pl.BlockSpec((br, BLOCK), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_bm25_kernel, k1=k1),
        grid=(n // br,),
        in_specs=[lanes, col, col, lanes, col, col, col],
        out_specs=[lanes] * 3,
        out_shape=_lane_out(n),
        interpret=interpret,
    )(_words(packed_docs, n), _col(bw_docs, n), _col(first_doc, n),
      _words(packed_tf, n), _col(bw_tf, n), _col(idf, n, jnp.float32),
      _col(active, n))
    return [o[:nb] for o in out]


@functools.partial(jax.jit,
                   static_argnames=("k1", "k", "block_rows", "interpret"))
def bm25_blocks_midgrid_pallas(packed_docs, bw_docs, first_doc, packed_tf,
                               bw_tf, idf, active, rows, ubf, theta_lanes,
                               norm_max, *, k1: float = 0.9, k: int = 10,
                               block_rows: int = 8,
                               interpret: bool):
    """-> (docids, tf, num, skip): the plain kernel's outputs with
    midgrid-skipped blocks zeroed, plus the per-block (S,) skip flags.
    ``rows`` attributes each compacted block to its query row, ``ubf``
    is the block's stored full-score upper bound, ``theta_lanes``
    (1, 128) seeds the per-row carry (lane j = row j), ``norm_max`` is a
    scalar — the max doc norm, making num / (tf + norm_max) a pessimistic
    realized partial for every lane. Defaults to a SHORT grid step so the
    carry feeds back within typical survivor buckets."""
    nb = packed_docs.shape[0]
    br, n = grid_rows(nb, block_rows)
    col = pl.BlockSpec((br, 1), lambda i: (i, 0))
    lanes = pl.BlockSpec((br, BLOCK), lambda i: (i, 0))
    carry = pl.BlockSpec((1, BLOCK), lambda i: (0, 0))
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))
    out = pl.pallas_call(
        functools.partial(_bm25_kernel_midgrid, k1=k1, k=k),
        grid=(n // br,),
        in_specs=[lanes, col, col, lanes, col, col, col, col, col, carry,
                  scalar],
        out_specs=[lanes, lanes, lanes, col, carry],
        out_shape=_lane_out(n) + [
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, BLOCK), jnp.float32)],
        interpret=interpret,
    )(_words(packed_docs, n), _col(bw_docs, n), _col(first_doc, n),
      _words(packed_tf, n), _col(bw_tf, n), _col(idf, n, jnp.float32),
      _col(active, n), _col(rows, n), _col(ubf, n, jnp.float32),
      theta_lanes.astype(jnp.float32),
      jnp.asarray(norm_max, jnp.float32).reshape(1, 1))
    return [o[:nb] for o in out[:3]] + [out[3][:nb, 0]]
