"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp ref oracles,
shape/dtype sweeps + hypothesis round-trip properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.postings_pack import ref as pref
from repro.kernels.postings_pack.kernel import pack_pallas, unpack_pallas
from repro.kernels.bm25_blockmax.ref import bm25_blocks_ref
from repro.kernels.bm25_blockmax.kernel import bm25_blocks_pallas


@pytest.mark.parametrize("nb", [8, 64, 256])
@pytest.mark.parametrize("scale", [1, 7, 1000, 2 ** 20, 2 ** 31 - 1])
def test_pack_kernel_matches_ref(nb, scale):
    rng = np.random.default_rng(nb * 7 + scale % 97)
    d = jnp.asarray(rng.integers(0, scale, size=(nb, 128), dtype=np.int64)
                    .astype(np.uint32))
    p_ref, bw_ref = pref.pack_ref(d)
    p_k, bw_k = pack_pallas(d, block_rows=min(64, nb), interpret=True)
    np.testing.assert_array_equal(np.asarray(p_k).reshape(p_ref.shape),
                                  np.asarray(p_ref))
    np.testing.assert_array_equal(np.asarray(bw_k), np.asarray(bw_ref))
    u = unpack_pallas(p_k, bw_k, block_rows=min(64, nb), interpret=True)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(d))


def test_pack_edge_cases():
    zeros = jnp.zeros((128, 128), jnp.uint32)
    p, bw = pack_pallas(zeros, block_rows=128, interpret=True)
    assert (np.asarray(bw) == 0).all()
    maxed = jnp.full((128, 128), 0xFFFFFFFF, jnp.uint32)
    p, bw = pack_pallas(maxed, block_rows=128, interpret=True)
    assert (np.asarray(bw) == 32).all()
    np.testing.assert_array_equal(
        np.asarray(unpack_pallas(p, bw, block_rows=128, interpret=True)),
        np.asarray(maxed))


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 2 ** 32 - 1), min_size=128, max_size=128),
       st.integers(0, 10 ** 6))
def test_pack_roundtrip_property(vals, extra):
    d = np.asarray(vals, np.uint32).reshape(1, 128)
    d2 = np.full((1, 128), extra % (2 ** 32), np.uint32)
    arr = jnp.asarray(np.concatenate([d, d2]))
    p, bw = pref.pack_ref(arr)
    np.testing.assert_array_equal(np.asarray(pref.unpack_ref(p, bw)),
                                  np.asarray(arr))
    # compacted size never exceeds raw, never below max-bits bound
    assert float(pref.packed_bytes(bw)) <= arr.size * 4 + arr.shape[0] * 1


@pytest.mark.parametrize("nb", [8, 64, 256])
def test_bm25_skip_kernel_over_compacted_survivors(nb):
    """The rewritten skip kernel: grid over a COMPACTED survivor array
    (power-of-two sizes, the shapes ``compact_survivors`` emits), fused
    unpack + score — vs the jnp oracle in interpret mode."""
    rng = np.random.default_rng(nb + 1)
    deltas = rng.integers(0, 50, (nb, 128)).astype(np.uint32)
    deltas[:, 0] = 0
    tf = rng.integers(0, 30, (nb, 128)).astype(np.uint32)
    pd, bwd = pref.pack_ref(jnp.asarray(deltas))
    pt, bwt = pref.pack_ref(jnp.asarray(tf))
    first = jnp.asarray(rng.integers(0, 5000, nb).astype(np.int32))
    idf = jnp.asarray(rng.random(nb).astype(np.float32) * 4)
    act = jnp.asarray((rng.random(nb) < 0.8).astype(np.int32))
    doc, tf_o, num = bm25_blocks_pallas(pd, bwd, first, pt, bwt, idf, act,
                                        interpret=True)
    ref = bm25_blocks_ref(pd, bwd, first, pt, bwt, idf, act)
    for got, want in zip((doc, tf_o, num), ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)


def _compact_layout(packed, bw):
    """(compact words, exclusive-cumsum plane offsets) — the layout
    ``build_block_index(compact=True)`` stores."""
    words, coff = pref.compact_words(np.asarray(packed), np.asarray(bw))
    return jnp.asarray(words), jnp.asarray(coff)


def test_compact_planes_roundtrip():
    """compact_planes drops exactly the dead planes (rows == sum(bw))
    and expand_planes restores the fixed-stride form bit-identically,
    including bw=0 and bw=32 blocks."""
    rng = np.random.default_rng(50)
    vals = rng.integers(0, 1 << 16, (6, 128)).astype(np.uint32)
    vals[0] = 0                       # bw = 0
    vals[1] = 0xFFFFFFFF              # bw = 32
    packed, bw = pref.pack_ref(jnp.asarray(vals))
    packed, bw_np = np.asarray(packed), np.asarray(bw, np.int64)
    rows = pref.compact_planes(packed, bw_np)
    assert rows.shape == (int(bw_np.sum()), pref.WORDS_PER_PLANE)
    back = pref.expand_planes(rows, bw_np)
    np.testing.assert_array_equal(back, packed * (
        np.arange(32)[None, :, None] < bw_np[:, None, None]))
    np.testing.assert_array_equal(
        np.asarray(pref.unpack_fast(jnp.asarray(back), bw)), vals)


@pytest.mark.parametrize("nb", [4, 32, 37])
def test_bm25_compact_matches_plain_ref(nb):
    """The fused decompress-and-score stack: expand_rows_ref gather, the
    compact jnp oracle, and the Pallas compact kernel (interpret mode)
    all reproduce ``bm25_blocks_ref`` over the fixed-stride planes bit
    for bit — blocks decode inside the scoring computation without ever
    materializing the expanded form up front."""
    from repro.kernels.bm25_blockmax.kernel import bm25_blocks_compact_pallas
    from repro.kernels.bm25_blockmax.ops import bm25_blocks_compact
    from repro.kernels.bm25_blockmax.ref import (bm25_blocks_compact_ref,
                                                 expand_rows_ref)
    rng = np.random.default_rng(nb + 3)
    deltas = rng.integers(0, 50, (nb, 128)).astype(np.uint32)
    deltas[:, 0] = 0
    deltas[0] = 0                     # an all-zero-gap block (bw=0)
    tf = rng.integers(0, 30, (nb, 128)).astype(np.uint32)
    pd, bwd = pref.pack_ref(jnp.asarray(deltas))
    pt, bwt = pref.pack_ref(jnp.asarray(tf))
    cpl_d, coff_d = _compact_layout(pd, bwd)
    cpl_t, coff_t = _compact_layout(pt, bwt)
    first = jnp.asarray(rng.integers(0, 5000, nb).astype(np.int32))
    idf = jnp.asarray(rng.random(nb).astype(np.float32) * 4)
    act = jnp.asarray((rng.random(nb) < 0.8).astype(np.int32))
    want = bm25_blocks_ref(pd, bwd, first, pt, bwt, idf, act)
    np.testing.assert_array_equal(
        np.asarray(expand_rows_ref(cpl_d, coff_d, bwd)), np.asarray(pd) * (
            np.arange(32)[None, :, None] < np.asarray(bwd)[:, None, None]))
    for fn in (bm25_blocks_compact_ref, bm25_blocks_compact,
               lambda *a: bm25_blocks_compact_pallas(
                   *a, block_rows=4 if nb % 4 == 0 else 1,
                   interpret=True)):
        got = fn(cpl_d, coff_d, bwd, first, cpl_t, coff_t, bwt, idf, act)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("nb", [8, 16, 32, 64, 128, 256])
def test_bm25_midgrid_kernel_matches_ref_every_bucket(nb):
    """Mid-grid theta tightening, Pallas (interpret) vs the fori_loop
    oracle at every pow2 survivor bucket the compacted path emits:
    (docids, tf, num, skip) must agree bit for bit, skipped blocks must
    be zeroed in all three outputs, and blocks at/above the carry are
    untouched relative to the plain kernel."""
    from repro.kernels.bm25_blockmax.kernel import bm25_blocks_midgrid_pallas
    from repro.kernels.bm25_blockmax.ref import bm25_blocks_midgrid_ref
    rng = np.random.default_rng(nb + 9)
    deltas = rng.integers(0, 50, (nb, 128)).astype(np.uint32)
    deltas[:, 0] = 0
    tf = rng.integers(0, 30, (nb, 128)).astype(np.uint32)
    pd, bwd = pref.pack_ref(jnp.asarray(deltas))
    pt, bwt = pref.pack_ref(jnp.asarray(tf))
    first = jnp.asarray(rng.integers(0, 5000, nb).astype(np.int32))
    idf = jnp.asarray((rng.random(nb) * 4).astype(np.float32))
    act = jnp.asarray((rng.random(nb) < 0.85).astype(np.int32))
    # a 4-query batch sharing the row space; stored UBs span the range the
    # running carry reaches, so real skips occur mid-grid
    rows = jnp.asarray(rng.integers(0, 4, nb).astype(np.int32))
    ubf = jnp.asarray((rng.random(nb) * 8).astype(np.float32))
    theta = jnp.zeros((1, 128), jnp.float32).at[0, :4].set(
        jnp.asarray(rng.random(4).astype(np.float32)))
    nmax = jnp.float32(0.9 * (1.0 - 0.4 + 0.4 * 2.0))
    args = (pd, bwd, first, pt, bwt, idf, act, rows, ubf, theta, nmax)
    skipped_any = False
    for br, k in ((4, 3), (8, 10)):
        want = bm25_blocks_midgrid_ref(*args, k=k, block_rows=br)
        got = bm25_blocks_midgrid_pallas(*args, k=k, block_rows=br,
                                         interpret=True)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        skip = np.asarray(want[3]) > 0
        skipped_any |= bool(skip.any())
        for out in want[:3]:
            assert (np.asarray(out)[skip] == 0).all()
        keep = (np.asarray(act) > 0) & ~skip
        plain = bm25_blocks_ref(pd, bwd, first, pt, bwt, idf, act)
        for w, p in zip(want[:3], plain):
            np.testing.assert_array_equal(np.asarray(w)[keep],
                                          np.asarray(p)[keep])
    assert nb < 32 or skipped_any, "carry never engaged on a large bucket"


@pytest.mark.parametrize("nb", [4, 32])
def test_bm25_kernel_matches_ref(nb):
    rng = np.random.default_rng(nb)
    deltas = rng.integers(0, 50, (nb, 128)).astype(np.uint32)
    deltas[:, 0] = 0
    tf = rng.integers(1, 30, (nb, 128)).astype(np.uint32)
    pd, bwd = pref.pack_ref(jnp.asarray(deltas))
    pt, bwt = pref.pack_ref(jnp.asarray(tf))
    first = jnp.asarray(rng.integers(0, 1000, nb).astype(np.int32))
    idf = jnp.asarray(rng.random(nb).astype(np.float32) * 3)
    act = jnp.asarray((rng.random(nb) < 0.7).astype(np.int32))
    ref = bm25_blocks_ref(pd, bwd, first, pt, bwt, idf, act)
    ker = bm25_blocks_pallas(pd, bwd, first, pt, bwt, idf, act,
                             block_rows=min(4, nb), interpret=True)
    for r, k in zip(ref, ker):
        np.testing.assert_allclose(np.asarray(k), np.asarray(r), rtol=1e-6)
    # semantic check: docids are prefix sums of deltas where active
    docids = np.asarray(ker[0])
    expect = np.asarray(first)[:, None] + np.cumsum(deltas, axis=1)
    mask = np.asarray(act) > 0
    np.testing.assert_array_equal(docids[mask], expect[mask])
    assert (docids[~mask] == 0).all()
