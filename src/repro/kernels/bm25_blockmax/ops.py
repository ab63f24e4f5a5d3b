"""Dispatcher for the fused BM25 block scoring op.

``bm25_blocks`` receives the block array the evaluation selected — the
full candidate grid on the dense oracle path, or the compacted
bucket-padded survivor array on the production pruned path
(``core/query.py``) — and returns per-lane (docids, tf, num). On TPU the
real Pallas skip kernel runs (grid over the compacted blocks); elsewhere
the pure-jnp reference does, which on the compacted path is already
survivor-proportional because the caller gathered the survivors first.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.bm25_blockmax.kernel import (bm25_blocks_compact_pallas,
                                                bm25_blocks_midgrid_pallas,
                                                bm25_blocks_pallas)
from repro.kernels.bm25_blockmax.ref import (bm25_blocks_compact_ref,
                                             bm25_blocks_midgrid_ref,
                                             bm25_blocks_ref)

# Module-level jit so the midgrid ref's fori_loop compiles ONCE per
# shape set: the ref builds a fresh ``step`` closure every call, and an
# un-jitted fori_loop keys its executable cache on that closure's
# identity — without this wrapper every midgrid call recompiles the
# whole scan.
_midgrid_ref_jit = functools.partial(
    jax.jit, static_argnames=("k1", "k", "block_rows"))(
        bm25_blocks_midgrid_ref)


def bm25_blocks(packed_docs, bw_docs, first_doc, packed_tf, bw_tf, idf,
                active, *, k1: float = 0.9):
    if jax.default_backend() == "tpu":
        return bm25_blocks_pallas(packed_docs, bw_docs, first_doc, packed_tf,
                                  bw_tf, idf, active, k1=k1,
                                  interpret=False)
    return bm25_blocks_ref(packed_docs, bw_docs, first_doc, packed_tf, bw_tf,
                           idf, active, k1=k1)


def bm25_blocks_compact(cwords_docs, coff_docs, bw_docs, first_doc,
                        cwords_tf, coff_tf, bw_tf, idf, active, *,
                        k1: float = 0.9):
    """Fused decompress-and-score over the COMPACT index layout: the
    selected blocks are decoded straight from the compressed bit-plane
    words. On TPU the Pallas grid fetches each block's words in-kernel
    (the decoded fixed-stride form never round-trips through HBM);
    elsewhere the jnp reference gathers + expands per selected block
    inside the same jitted computation — survivor-proportional on the
    compacted pruned path because the caller compacted first."""
    if jax.default_backend() == "tpu":
        return bm25_blocks_compact_pallas(cwords_docs, coff_docs, bw_docs,
                                          first_doc, cwords_tf, coff_tf,
                                          bw_tf, idf, active, k1=k1,
                                          interpret=False)
    return bm25_blocks_compact_ref(cwords_docs, coff_docs, bw_docs,
                                   first_doc, cwords_tf, coff_tf, bw_tf,
                                   idf, active, k1=k1)


def bm25_blocks_midgrid(packed_docs, bw_docs, first_doc, packed_tf, bw_tf,
                        idf, active, rows, ubf, theta_lanes, norm_max, *,
                        k: int, k1: float = 0.9, block_rows: int = 8):
    """Midgrid theta-tightening block scoring: (docids, tf, num, skip)
    with blocks whose stored full-score UB fell below the running
    per-row k-th-best carry zeroed and flagged. On TPU the Pallas grid
    runs compiled; elsewhere the jnp oracle (a fori_loop over the same
    grid steps) — bit-identical by the parity tests."""
    if jax.default_backend() == "tpu":
        return bm25_blocks_midgrid_pallas(
            packed_docs, bw_docs, first_doc, packed_tf, bw_tf, idf, active,
            rows, ubf, theta_lanes, norm_max, k1=k1, k=k,
            block_rows=block_rows, interpret=False)
    return _midgrid_ref_jit(
        packed_docs, bw_docs, first_doc, packed_tf, bw_tf, idf, active,
        rows, ubf, theta_lanes, norm_max, k1=k1, k=k,
        block_rows=block_rows)

