"""Pure-jnp oracle for the fused block-max BM25 scoring kernel.

Per postings block (128 lanes): unpack doc-id deltas (lane-blocked PFor),
prefix-sum them onto the block's first doc id, unpack term frequencies,
and emit the BM25 numerator idf * (k1+1) * tf. Skipped blocks (block-max
pruning decided upstream) emit zeros. The production pruned path hands
this a COMPACTED survivor array, so on the CPU backend the jnp work is
proportional to survivors too (``core/query.py::compact_survivors``).

The caller finishes the score with the per-doc length norm:
  score += num / (tf + k1 * (1 - b + b * dl[doc] / avgdl))
which needs a doc-indexed gather and so lives outside the kernel.

Packed blocks arrive in the index's device layout, (NB, 128) words
(lane ``4 * plane + word``), or in the equivalent (NB, 32, 4) form.

``bm25_blocks_compact_ref`` is the fused decompress-and-score oracle
over the COMPACT storage layout: instead of the fixed-stride buffer,
the index holds only the live bit-plane words (``sum(bw)`` rows of 4
words — the exact bytes the storage codec writes, cut into 128-word
rows by ``postings_pack.ref.compact_words``) plus per-block row
offsets. Each selected block's planes are gathered straight out of the
compressed words and expanded inside the (jitted) computation — on CPU
this is the jnp-over-compacted fallback that decodes per survivor
block; on TPU the Pallas variant does the same expansion inside the
kernel grid, so the fixed-stride decoded form never exists in HBM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.postings_pack.ref import unpack_fast

BLOCK = 128


def _unpack(packed, bw):
    return unpack_fast(packed.reshape(-1, 32, 4), bw)


def bm25_blocks_ref(packed_docs, bw_docs, first_doc, packed_tf, bw_tf,
                    idf, active, k1: float = 0.9):
    """-> (docids (NB,128) int32, tf (NB,128) f32, num (NB,128) f32)."""
    deltas = _unpack(packed_docs, bw_docs).astype(jnp.int32)
    docids = first_doc[:, None] + jnp.cumsum(deltas, axis=1)
    tf = _unpack(packed_tf, bw_tf).astype(jnp.float32)
    num = idf[:, None] * (k1 + 1.0) * tf
    act = (active > 0)[:, None]
    return (jnp.where(act, docids, 0),
            jnp.where(act, tf, 0.0),
            jnp.where(act, num, 0.0))


def expand_rows_ref(cwords, coff, bw):
    """Gather-expand compacted bit-plane words into the fixed-stride form.

    ``cwords`` (M, 128) uint32 is every block's live planes back to back
    as one word stream (``postings_pack.ref.compact_words``: block-major,
    plane-major, zero-padded past the end); ``coff`` (S,) is each
    selected block's first plane row (its words start at ``4 * coff``);
    ``bw`` (S,) its plane count. Returns (S, 32, 4) uint32 with dead
    planes (>= bw) zeroed, exactly what ``unpack_fast`` consumes.
    """
    j = jnp.arange(BLOCK)
    valid = (j // 4)[None, :] < bw[:, None]
    w = cwords.reshape(-1)[jnp.where(valid, coff[:, None] * 4 + j, 0)]
    return jnp.where(valid, w, jnp.uint32(0)).reshape(-1, 32, 4)


def bm25_blocks_compact_ref(cwords_docs, coff_docs, bw_docs, first_doc,
                            cwords_tf, coff_tf, bw_tf, idf, active,
                            k1: float = 0.9):
    """Fused decompress-and-score over compact storage: expand the
    selected blocks' planes from the compressed words, then the standard
    block scoring — same (docids, tf, num) contract as
    ``bm25_blocks_ref``, asserted bit-identical in tests."""
    pd = expand_rows_ref(cwords_docs, coff_docs, bw_docs)
    pt = expand_rows_ref(cwords_tf, coff_tf, bw_tf)
    return bm25_blocks_ref(pd, bw_docs, first_doc, pt, bw_tf, idf, active,
                           k1=k1)


def _kth_lane_partial(part, k: int):
    """Per block row, a lower bound on the k-th largest of its 128 lane
    values: k-1 rounds of (take the max, retire every lane equal to it),
    then the max of what is left, floored at 0. Retiring ties retires
    several lanes at once, which only drives the result DOWN — still a
    valid k-th-best lower bound. Each positive lane is a distinct doc
    (pad lanes repeat the last doc id but carry tf 0 -> partial 0), so a
    positive result is witnessed by k distinct docs."""
    cur = part
    for _ in range(max(k - 1, 0)):
        m = cur.max(axis=1, keepdims=True)
        cur = jnp.where(cur == m, -1.0, cur)
    return jnp.maximum(cur.max(axis=1), 0.0)


def bm25_blocks_midgrid_ref(packed_docs, bw_docs, first_doc, packed_tf,
                            bw_tf, idf, active, rows, ubf, theta_lanes,
                            norm_max, k1: float = 0.9, k: int = 10,
                            block_rows: int = 8):
    """Oracle for the midgrid theta-tightening kernel: identical step
    semantics to the Pallas grid, expressed as a ``fori_loop`` over the
    same ``block_rows``-sized steps.

    Per step, in order: (1) read the running per-row k-th-best carry L
    (seeded from ``theta_lanes``, lane j = row j's external bound) and
    mark every ACTIVE block whose stored full-score UB ``ubf`` is
    strictly below its row's L as skipped — decisions within one step
    never see that step's own updates; (2) fold the KEPT blocks' k-th
    largest pessimistic lane partial ``num / (tf + norm_max)`` into L by
    row. Outputs are the plain kernel's (docids, tf, num) with skipped
    blocks zeroed, plus the (S,) skip flags. Bit-identity with the
    Pallas kernel is asserted in tests at every pow2 survivor bucket."""
    S = packed_docs.shape[0]
    block_rows = min(block_rows, S)
    assert S % block_rows == 0, (S, block_rows)
    deltas = _unpack(packed_docs, bw_docs).astype(jnp.int32)
    docids = first_doc.astype(jnp.int32)[:, None] + jnp.cumsum(deltas,
                                                               axis=1)
    tf_all = _unpack(packed_tf, bw_tf).astype(jnp.float32)
    num_all = idf.astype(jnp.float32)[:, None] * (k1 + 1.0) * tf_all
    act_all = active > 0
    eq_all = rows.astype(jnp.int32)[:, None] \
        == jnp.arange(BLOCK, dtype=jnp.int32)[None, :]          # (S, 128)
    ubf = ubf.astype(jnp.float32)
    nmax = jnp.asarray(norm_max, jnp.float32)

    def step(i, carry):
        L, skip_acc = carry
        sl = i * block_rows
        eq = jax.lax.dynamic_slice_in_dim(eq_all, sl, block_rows, 0)
        act = jax.lax.dynamic_slice_in_dim(act_all, sl, block_rows, 0)
        ub = jax.lax.dynamic_slice_in_dim(ubf, sl, block_rows, 0)
        tf = jax.lax.dynamic_slice_in_dim(tf_all, sl, block_rows, 0)
        num = jax.lax.dynamic_slice_in_dim(num_all, sl, block_rows, 0)
        l_row = jnp.sum(jnp.where(eq, L, 0.0), axis=1)
        skip = act & (ub < l_row)
        keep2 = (act & ~skip)[:, None]
        part = jnp.where(keep2 & (tf > 0), num / (tf + nmax), 0.0)
        kth = _kth_lane_partial(part, k)
        L = jnp.maximum(L, jnp.where(eq, kth[:, None], 0.0
                                     ).max(axis=0, keepdims=True))
        skip_acc = jax.lax.dynamic_update_slice_in_dim(
            skip_acc, skip.astype(jnp.int32), sl, 0)
        return L, skip_acc

    _, skip = jax.lax.fori_loop(
        0, S // block_rows, step,
        (theta_lanes.astype(jnp.float32), jnp.zeros(S, jnp.int32)))
    keep2 = (act_all & (skip == 0))[:, None]
    return (jnp.where(keep2, docids, 0), jnp.where(keep2, tf_all, 0.0),
            jnp.where(keep2, num_all, 0.0), skip)
