"""Serving driver: batched prefill + decode with the KV cache, plus the
retrieval path (inverted-index BM25 — the paper's serving counterpart).

  python -m repro.launch.serve --arch gemma2-9b --requests 4 --gen 16
  python -m repro.launch.serve --mode retrieval --requests 64 --slots 32
  python -m repro.launch.serve --mode retrieval --index-dir /tmp/idx

Retrieval mode exercises the full write-read-decoupled read path: index
batches, ``refresh()`` a live (un-finalized) searcher, serve a batched
query stream through the fixed-slot ``QueryScheduler``, keep indexing,
refresh again (cached readers) and serve the grown corpus — then the
document lifecycle: ``--deletes N`` tombstones N served docs and
``--updates M`` replaces M more (delete + re-add), the next refresh is
asserted to never return a deleted doc, and with ``--index-dir`` the
tombstones are committed as ``.liv`` delete generations and recovered
from disk. ``--refresh-every S`` serves from the indexer's background
NRT refresh daemon instead of manual refreshes.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as TF
from repro.models.transformer import MeshInfo


def generate(cfg, params, prompts, gen_tokens: int, mesh=None,
             temperature: float = 0.0):
    """prompts: (B, S) int32, right-padded with 0; returns (B, gen) tokens."""
    mi = MeshInfo() if mesh is None else MeshInfo(mesh=mesh)
    B, S = prompts.shape
    pad_to = S + gen_tokens
    prefill = jax.jit(lambda p, t: TF.prefill(p, t, cfg, mi, pad_to=pad_to))
    decode = jax.jit(lambda p, c, l, t: TF.decode_step(p, c, l, t, cfg, mi))
    caches, logits = prefill(params, prompts)
    lengths = (prompts > 0).sum(axis=1).astype(jnp.int32)
    # NOTE: per-request lengths — rope positions and cache writes are
    # per-row, so ragged prompts decode correctly.
    out = []
    last = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out.append(last)
    for i in range(gen_tokens - 1):
        caches, logits = decode(params, caches, lengths + i, last)
        last = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(last)
    return jnp.stack(out, axis=1)


def serve_retrieval(args):
    """BM25 serving over live segments via the fixed-slot QueryScheduler."""
    from repro.core.indexer import DistributedIndexer
    from repro.data.corpus import TINY, SyntheticCorpus
    from repro.serving.query_scheduler import QueryRequest, QueryScheduler

    cfg = get_arch("lucene-envelope").smoke
    corpus = SyntheticCorpus(TINY, doc_buffer_len=cfg.doc_len)
    target_dir = None
    if args.index_dir:
        from repro.storage import FSDirectory
        target_dir = FSDirectory(args.index_dir)
    ix = DistributedIndexer(cfg=cfg, target_dir=target_dir,
                            refresh_every=args.refresh_every)
    recovered_docs = sum(s.live_doc_count
                        for s in ix.merger.live_segments()) \
        if target_dir else 0
    for i in range(4):
        ix.index_batch(corpus.batch(i, 32))
    if target_dir is not None:
        gen = ix.commit()
        # recover from the just-committed bytes: the searcher we serve is
        # built from storage, not from the in-memory segments
        from repro.storage import open_searcher
        gen_r, searcher = open_searcher(target_dir)
        print(f"durable index: commit gen {gen} "
              f"({recovered_docs} docs recovered at startup); serving "
              f"{searcher.n_docs} docs recovered from {args.index_dir}")
    else:
        searcher = ix.refresh()
    sched = QueryScheduler(searcher=searcher, slots=args.slots,
                           max_terms=args.query_terms, k=args.topk)

    rng = np.random.default_rng(0)
    vocab = np.unique(corpus.batch(0, 32))[1:]

    def make_reqs(n, rid0=0):
        return [QueryRequest(rid=rid0 + i, terms=rng.choice(
                    vocab, size=args.query_terms, replace=False),
                    k=args.topk)
                for i in range(n)]

    # warm up the per-segment compiles on throwaway queries, so the timed
    # section measures steady-state even when --requests < --slots
    for r in make_reqs(args.slots, rid0=-args.slots):
        sched.submit(r)
    sched.step()
    reqs = make_reqs(args.requests)
    for r in reqs:
        sched.submit(r)
    t0 = time.time()
    done = sched.run_to_completion()
    dt = max(time.time() - t0, 1e-9)
    print(f"retrieval: {searcher.n_segments} live segments, "
          f"{searcher.n_docs} docs; served {len(done)} queries "
          f"in {dt*1000:.0f}ms ({len(done)/dt:.0f} qps steady-state)")
    ps = sched.prune_stats
    print(f"pruning: {ps.blocks_candidate} candidate blocks -> "
          f"{ps.blocks_survived} survived -> {ps.blocks_scored} scored "
          f"(skip rate {ps.skip_rate:.2f}, "
          f"{ps.segments_skipped} segments skipped)")

    # keep indexing, refresh, serve again — search-while-indexing
    for i in range(4, 8):
        ix.index_batch(corpus.batch(i, 32))
    sched.swap_searcher(ix.refresh())
    print(f"refresh: {ix.stats.last_refresh_s*1000:.1f}ms, "
          f"reader builds {ix.reader_cache.builds} "
          f"(cache hits {ix.reader_cache.hits})")
    for r in reqs[:args.slots]:
        r.done = False
        sched.submit(r)
    done = sched.run_to_completion()
    top = f"top score {float(done[0].scores[0]):.3f}" if done else "no queries"
    print(f"post-refresh: {sched.searcher.n_docs} docs searchable; {top}")

    # --- document lifecycle: delete + update live docs, serve again ------
    if args.deletes or args.updates:
        served = np.unique(np.concatenate(
            [r.doc_ids for r in done if r.doc_ids is not None]))
        served = served[served >= 0]
        del_ids = served[:args.deletes]
        upd_ids = served[args.deletes:args.deletes + args.updates]
        ix.delete(del_ids)
        for d in upd_ids:
            ix.update(int(d), corpus.batch(int(d) % 8, 32)[int(d) % 32])
        if args.refresh_every:
            # the NRT daemon folds the deletes in and swaps ix.searcher;
            # wait for TWO ticks instead of refreshing by hand — a tick
            # already in flight when we read r0 may predate the acks, but
            # the one after it must have started after them
            r0 = ix.stats.refreshes
            deadline = time.time() + max(40 * args.refresh_every, 10.0)
            while ix.stats.refreshes < r0 + 2 and time.time() < deadline:
                time.sleep(args.refresh_every / 4)
            sched.swap_searcher(ix.searcher)
        else:
            sched.swap_searcher(ix.refresh())
        for r in reqs[:args.slots]:
            r.done = False
            sched.submit(r)
        done2 = sched.run_to_completion()
        got = np.concatenate([r.doc_ids for r in done2]) if done2 \
            else np.zeros(0, np.int64)
        gone = set(del_ids.tolist()) | set(upd_ids.tolist())
        assert not (set(got[got >= 0].tolist()) & gone), \
            "a tombstoned doc surfaced after its delete was acknowledged"
        rep = ix.envelope_report()
        print(f"lifecycle: deleted {len(del_ids)} + updated {len(upd_ids)} "
              f"docs; {sched.searcher.n_docs} live "
              f"({rep['deleted_docs']} tombstoned awaiting merge); "
              f"no deleted doc served")
        if target_dir is not None:
            gen = ix.commit()
            from repro.storage import open_searcher as open_s
            _, s_rec = open_s(FSDirectory(args.index_dir))
            # compare against the indexer's live count, not the served
            # snapshot: commit() flushes, so it may surface update
            # re-adds a daemon (flush=False) snapshot hasn't seen yet
            n_live = sum(s.live_doc_count
                         for s in ix.merger.live_segments())
            assert s_rec.n_docs == n_live, (s_rec.n_docs, n_live)
            livs = [f for f in FSDirectory(args.index_dir).list_files()
                    if f.endswith(".liv")]
            print(f"lifecycle durable: commit gen {gen}, "
                  f"{len(livs)} .liv delete generation(s), recovery "
                  f"serves {s_rec.n_docs} live docs")
    ix.close()
    return done


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("lm", "retrieval"), default="lm")
    ap.add_argument("--arch", default="gemma2-9b")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--query-terms", type=int, default=4)
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--deletes", type=int, default=8,
                    help="retrieval mode: tombstone this many served docs "
                         "and prove the next snapshot never returns them")
    ap.add_argument("--updates", type=int, default=4,
                    help="retrieval mode: replace this many served docs "
                         "(delete + re-add under the flush lock)")
    ap.add_argument("--refresh-every", type=float, default=0.0,
                    help="retrieval mode: run the NRT refresh daemon at "
                         "this period (s) and serve from its snapshots")
    ap.add_argument("--index-dir", default=None,
                    help="retrieval mode: durable FSDirectory index — "
                         "commit, recover from disk, then serve (resumes "
                         "an existing index at its last commit point)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.mode == "retrieval":
        return serve_retrieval(args)

    entry = get_arch(args.arch)
    cfg = entry.smoke
    params = TF.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(
        1, cfg.vocab_size, (args.requests, args.prompt_len)), jnp.int32)
    t0 = time.time()
    toks = generate(cfg, params, prompts, args.gen)
    dt = time.time() - t0
    print(f"arch={cfg.name} served {args.requests} requests x "
          f"{args.gen} tokens in {dt:.2f}s "
          f"({args.requests * args.gen / dt:.1f} tok/s)")
    print("sample generations:", np.asarray(toks[:2, :8]))
    return toks


if __name__ == "__main__":
    main()
