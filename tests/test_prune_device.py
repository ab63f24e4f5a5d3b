"""The pruning decision on the device (``core/query.py`` ``bound_test``,
float32) against the retained exact host test (``_bound_test``, float64).

The float32 test keeps a block iff ``bound * (1 + slack) > theta`` and
calls a term non-essential only if ``csum * (1 + slack) <= theta``, so
for every batch:

- its survivors contain the exact test's, and every extra one survives
  the exact test at theta lowered by the band (2 * slack: the slack plus
  the float32 rounding it covers);
- its non-essential terms are among the exact test's, and contain those
  the exact test finds at the lowered theta;
- ``blocks_margin_kept`` counts exactly the blocks in
  ``bound * (1 + slack) > theta >= bound``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.array import ArrayImpl

from repro.configs.registry import get_arch
from repro.core.indexer import DistributedIndexer
from repro.core.query import _bound_test, bound_slack, bound_test
from repro.core.searcher import IndexSearcher
from repro.data.corpus import TINY, SyntheticCorpus

CASES = ("mixed", "on_bound", "nonessential")


def _metadata(rng, B, Q, MB):
    """Random (B, Q, MB) metadata in the metadata pass's layout: each
    term row a prefix of real blocks (some rows empty, some full) with
    sorted, disjoint doc ranges, zero UB and garbage extents on pads."""
    D = 8 * MB + 16
    ub = np.zeros((B, Q, MB), np.float32)
    in_term = np.zeros((B, Q, MB), bool)
    bf = rng.integers(-5, 2 ** 31 - 1, (B, Q, MB)).astype(np.int32)
    bl = rng.integers(-5, 2 ** 31 - 1, (B, Q, MB)).astype(np.int32)
    for b in range(B):
        for t in range(Q):
            n = int(rng.choice([0, MB, rng.integers(0, MB + 1)]))
            v = np.sort(rng.choice(D, 2 * n, replace=False))
            first, last = v[0::2], v[1::2]
            last = np.where(rng.random(n) < 0.2, first, last)
            bf[b, t, :n], bl[b, t, :n] = first, last
            in_term[b, t, :n] = True
            scale = rng.choice([0.5, 2.0, 8.0])
            ub[b, t, :n] = np.where(rng.random(n) < 0.1, 0.0,
                                    rng.random(n) * scale)
    return ub, in_term, bf, bl


@functools.lru_cache(maxsize=None)
def _device(bmw):
    return jax.jit(functools.partial(bound_test, bmw=bmw))


def _run_device(meta, theta, bmw):
    surv, bound, ness, n_margin = jax.device_get(_device(bmw)(
        *map(jnp.asarray, meta), jnp.asarray(theta, jnp.float32)))
    return surv, bound, ness, int(n_margin)


def _run_oracle(meta, theta, bmw):
    ub, in_term, bf, bl = meta
    B, Q, MB = ub.shape
    surv, bound, ness = _bound_test(
        ub.reshape(B, -1).astype(np.float64), in_term.reshape(B, -1),
        bf.astype(np.int64), bl.astype(np.int64),
        np.asarray(theta, np.float64), Q, bmw)
    return surv.reshape(B, Q, MB), bound.reshape(B, Q, MB), ness


def _thetas(rng, case, meta, bound_d):
    """One theta per batch row: on one block's float32 bound exactly, above
    every term combination (all terms non-essential), or, mixed, either
    of those, a quantile of the row's bounds, or 0. Also returns how many
    rows hold a theta on a bound."""
    ub, in_term, _, _ = meta
    B = ub.shape[0]
    theta = np.zeros(B, np.float32)
    on_bound = 0
    for b in range(B):
        kind = case if case != "mixed" else rng.choice(
            ["quantile", "on_bound", "nonessential", "zero"])
        pos = np.flatnonzero(in_term[b] & (bound_d[b] > 0))
        if kind == "nonessential":
            theta[b] = ub[b].max(axis=1).sum() * 2 + 1
        elif kind == "on_bound" and pos.size:
            theta[b] = bound_d[b].reshape(-1)[rng.choice(pos)]
            on_bound += 1
        elif kind == "quantile" and pos.size:
            theta[b] = np.quantile(bound_d[b].reshape(-1)[pos], rng.random())
    return theta, on_bound


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("MB", [1, 8, 512])
@pytest.mark.parametrize("Q", [1, 3, 8])
@pytest.mark.parametrize("B", [1, 32])
@pytest.mark.parametrize("bmw", [True, False])
def test_device_bound_test_against_exact_oracle(bmw, B, Q, MB, case):
    rng = np.random.default_rng([B, Q, MB, int(bmw), CASES.index(case)])
    meta = _metadata(rng, B, Q, MB)
    in_term = meta[1]
    # the bound does not depend on theta: read it once to place theta
    _, bound_d, _, _ = _run_device(meta, np.zeros(B), bmw)
    theta, on_bound = _thetas(rng, case, meta, bound_d)
    surv_d, bound_d, ness_d, n_margin = _run_device(meta, theta, bmw)
    surv_o, bound_o, ness_o = _run_oracle(meta, theta, bmw)
    slack = bound_slack(Q)
    theta_lo = theta.astype(np.float64) * (1 - 2 * slack)
    surv_lo, _, ness_lo = _run_oracle(meta, theta_lo, bmw)

    # the float32 bound is the exact one up to rounding
    np.testing.assert_allclose(bound_d[in_term], bound_o[in_term],
                               rtol=slack / 2, atol=0)
    # survivors: exact <= device <= exact at the lowered theta
    assert not (surv_o & ~surv_d).any(), "the device dropped a survivor"
    assert not (surv_d & ~surv_lo).any(), "a survivor outside the band"
    assert not (surv_d & ~in_term).any()
    # non-essential terms: exact at the lowered theta <= device <= exact
    assert not (ness_d & ~ness_o).any(), "non-essential beyond the oracle"
    assert not (ness_lo & ~ness_d).any()
    # blocks kept only by the slack, recomputed from the device's bound
    t3 = theta[:, None, None]
    band = in_term & (bound_d * np.float32(1 + slack) > t3) & (t3 >= bound_d)
    assert n_margin == int(band.sum())
    if on_bound:
        assert n_margin > 0
    if case == "nonessential":
        assert not surv_d.any()
        assert ness_d[in_term.any(2)].all() if bmw else not ness_d.any()


@pytest.fixture
def served_index():
    """Three segments of the smoke corpus, and a vocabulary to query."""
    cfg = get_arch("lucene-envelope").smoke
    corpus = SyntheticCorpus(TINY, doc_buffer_len=cfg.doc_len)
    ix = DistributedIndexer(cfg=cfg)
    tokens = [corpus.batch(i, 32) for i in range(3)]
    for b in tokens:
        ix.index_batch(b)
    yield ix, np.unique(np.concatenate(tokens)[np.concatenate(tokens) > 0])
    ix.close()


@pytest.mark.parametrize("deletes,k", [(False, 5), (True, 5), (False, 40)])
def test_pruned_search_fetches_only_counts_and_results(served_index,
                                                       monkeypatch,
                                                       deletes, k):
    """A served batch over a multi-segment searcher crosses to the host
    only through explicit ``jax.device_get`` calls: per visited segment
    the decision's counts and the segment's top-k, then the merged top-k.
    Covers the midgrid scorer (k=5, no deletes), the tombstone scorer and
    the plain scorer; results equal the dense path's."""
    ix, vocab = served_index
    if deletes:
        ix.delete(np.arange(0, 96, 5))
    searcher = ix.refresh()
    assert len(searcher.readers) == 3
    dense = IndexSearcher(readers=searcher.readers, k1=searcher.k1,
                          b=searcher.b, prune=False)
    rng = np.random.default_rng(k + deletes)
    qb = np.stack([rng.choice(vocab, 3, replace=False) for _ in range(4)]
                  ).astype(np.int32)
    searcher.search_batched(qb, k)          # compile outside the guard
    before = searcher.prune_stats.snapshot()

    explicit = []
    device_get = jax.device_get

    def counted_get(x):
        explicit.append(x)
        explicit_depth.append(1)
        try:
            return device_get(x)
        finally:
            explicit_depth.pop()

    explicit_depth = []
    value = ArrayImpl._value

    def implicit_guard(self):
        # the CPU backend hands arrays to numpy without a copy, which the
        # transfer guard does not see: refuse any fetch but device_get's
        assert explicit_depth, "implicit device-to-host fetch"
        return value.fget(self)

    monkeypatch.setattr(jax, "device_get", counted_get)
    monkeypatch.setattr(ArrayImpl, "_value", property(implicit_guard))
    with jax.transfer_guard_device_to_host("disallow"):
        v, i = searcher.search_batched(qb, k)
    monkeypatch.undo()
    visited = searcher.prune_stats.delta(before).segments_visited
    assert visited >= 1 and len(explicit) == 2 * visited + 1
    v_d, i_d = dense.search_batched(qb, k)
    assert np.array_equal(v, np.asarray(v_d))
    for row_v, row_i, d_v, d_i in zip(v, i, np.asarray(v_d),
                                      np.asarray(i_d)):
        # ties may order ids differently (and zero-score slots hold any
        # doc): each positive score goes with its doc
        assert sorted(zip(row_v[row_v > 0], row_i[row_v > 0])) \
            == sorted(zip(d_v[d_v > 0], d_i[d_v > 0]))
