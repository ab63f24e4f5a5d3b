"""The program's host spans (``repro/spans.py``) in a profiler trace: every
stage of a flush and commit, and of a served batch, lands inside its
parent span with its ids."""
import glob
import re
from collections import Counter
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.core.indexer import DistributedIndexer
from repro.data.corpus import TINY, SyntheticCorpus
from repro.serving.query_scheduler import QueryRequest, QueryScheduler
from repro import spans
from repro.spans import SPANS, span
from repro.storage import FSDirectory

CFG = get_arch("lucene-envelope").smoke
INGEST_LEAVES = {"flush.to_device", "flush.invert", "flush.to_host",
                 "flush.segment", "flush.account", "codec.encode",
                 "directory.write", "directory.sync"}
SERVE_LEAVES = {"search.plan", "prune.meta", "prune.probe", "prune.bound",
                "prune.compact", "score.survivors", "search.merge"}


def _events(log_dir):
    """(name, start, end, stats) of every program span in the trace."""
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in line.events if e.name in SPANS]
    return out


def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


def test_span_refuses_a_name_outside_the_table():
    with pytest.raises(AssertionError):
        span("no.such.span")


def test_each_span_is_opened_in_one_place():
    """Each stage has one owner: every name of the table is opened by
    exactly one ``span(...)`` call in the program."""
    src = Path(spans.__file__).parent
    opened = Counter(n for f in src.rglob("*.py")
                     for n in re.findall(r'\bspan\("([^"]+)"', f.read_text()))
    assert opened == {n: 1 for n in SPANS}


def test_flush_and_commit_spans(tmp_path):
    corpus = SyntheticCorpus(TINY, doc_buffer_len=CFG.doc_len)
    ix = DistributedIndexer(cfg=CFG, target_dir=FSDirectory(tmp_path / "i"))
    batch = corpus.batch(0, 16)
    try:
        with jax.profiler.trace(str(tmp_path / "trace")):
            ix.index_batch(batch)
            gen = ix.commit()
    finally:
        ix.close()
    evs = _events(tmp_path / "trace")
    flush, = [e for e in evs if e[0] == "indexer.flush"]
    commit, = [e for e in evs if e[0] == "indexer.commit"]
    store, = [e for e in evs if e[0] == "store.commit"]
    assert flush[3] == {"flush": 1, "docs": 16}
    assert commit[3] == {"gen": gen} and store[3] == {"gen": gen}
    assert _inside(store, commit)
    assert {e[0] for e in evs} >= INGEST_LEAVES
    for e in evs:
        if e[0] in INGEST_LEAVES:
            assert _inside(e, flush) or _inside(e, commit), e
    # the flush's stages are in its own span, the commit's sync in the
    # store's
    for name in ("flush.to_device", "flush.invert", "flush.to_host",
                 "flush.segment", "codec.encode"):
        assert any(_inside(e, flush) for e in evs if e[0] == name), name
    assert any(_inside(e, store) for e in evs if e[0] == "directory.sync")


def test_served_batch_spans(tmp_path):
    corpus = SyntheticCorpus(TINY, doc_buffer_len=CFG.doc_len)
    ix = DistributedIndexer(cfg=CFG)
    tokens = [corpus.batch(i, 32) for i in range(2)]
    for b in tokens:
        ix.index_batch(b)
    searcher = ix.refresh()
    ix.close()
    assert len(searcher.readers) == 2
    vocab = np.unique(np.concatenate(tokens)[np.concatenate(tokens) > 0])
    rng = np.random.default_rng(3)
    sched = QueryScheduler(searcher=searcher, slots=4, max_terms=3, k=5)
    reqs = [QueryRequest(rid=i, terms=rng.choice(vocab, 3, replace=False),
                         k=5) for i in range(6)]
    for r in reqs:
        sched.submit(r)
    with jax.profiler.trace(str(tmp_path / "trace")):
        done = sched.run_to_completion()
    assert len(done) == 6
    evs = _events(tmp_path / "trace")
    steps = [e for e in evs if e[0] == "sched.step"]
    assert [e[3] for e in steps] == [{"step": 1, "batch": 4},
                                     {"step": 2, "batch": 2}]
    segs = [e for e in evs if e[0] == "search.segment"]
    assert {e[3]["seg"] for e in segs} == {r.seg_id
                                           for r in searcher.readers}
    assert {e[0] for e in evs} >= SERVE_LEAVES
    for e in evs:
        if e[0] in SERVE_LEAVES:
            assert any(_inside(e, s) for s in steps), e
        if e[0] in SERVE_LEAVES - {"search.plan", "search.merge"}:
            assert any(_inside(e, s) for s in segs), e
