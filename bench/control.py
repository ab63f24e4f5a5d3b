#!/usr/bin/env python3
"""The controls that show each cell's comparison can fail.

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--seconds 30]

- Serving cells: the plain BM25 reference put in the program's place and
  computed in bfloat16, the precision below the configuration's float32,
  over the same collection and the same sample of the window's queries
  that a run checks; its gaps against the float64 reference are printed
  beside the limits (every seed has to exceed one).
- The ingest cell: the program with its own ``commit(flush=False)`` path
  switched on (a commit that publishes only what the flush budget has
  already flushed, breaking "every acknowledged document is in the next
  commit"), run through the whole cell; ``docs_missing`` has to exceed
  its limit.

One JSON line per seed on standard output. The benchmark's own runs do
not run this; it is how the limits' upper readings were taken.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def serve_control(cfg: dict, mix: dict, seed: int, seconds: float,
                  dtype=None) -> dict:
    """Gaps of the lower-precision reference on the window's queries."""
    import ml_dtypes
    import numpy as np
    from lib import traffic
    from lib.corpus import Corpus
    from lib.reference import TokenCollection, compare_topk, topk
    from lib.serve import check_sample
    dtype = ml_dtypes.bfloat16 if dtype is None else dtype
    corpus = Corpus(cfg["corpus"], seed)
    per = cfg["index"]["docs_per_shard"]
    batches = corpus.batches(0, cfg["n_docs"] // per, per)
    rng = np.random.default_rng((seed, 1))
    if mix["loop"] == "open":
        n = traffic.open_arrivals(mix["rate_qps"], seconds, rng).size
    else:
        n = mix["topics"]
    qs = traffic.queries(mix, corpus, n, rng)
    sample = [qs[j] for j in check_sample(qs, seed, mix["check_sample"])]
    coll = TokenCollection(batches)
    post = coll.postings(np.concatenate(sample),
                         (1 << int(cfg["corpus"]["vocab_bits"])) - 1)
    bm, k = cfg["bm25"], mix["k"]
    value_gap = id_gap = 0.0
    for q in sample:
        ids, s = coll.bm25_scores(q, post, bm["k1"], bm["b"])
        low = coll.bm25_scores(q, post, bm["k1"], bm["b"], dtype=dtype)
        v, i = topk(*low, k)
        g = compare_topk(v, i, ids, s, k)
        value_gap, id_gap = max(value_gap, g[0]), max(id_gap, g[1])
    return {"value_gap": value_gap, "id_gap": id_gap}


def ingest_control(workload: str, seed: int, seconds: float, **run_kw):
    """The cell's run with every commit publishing without a flush: only
    what the flush budget already flushed becomes durable, so the
    documents still buffered when the window's last commit returns are
    acknowledged and lost."""
    from repro.core.indexer import DistributedIndexer
    from lib import harness
    commit = DistributedIndexer.commit
    DistributedIndexer.commit = lambda self, flush=True: commit(self, False)
    try:
        return harness.run(workload, seed, seconds, False,
                           t_start=time.perf_counter(), **run_kw)
    finally:
        DistributedIndexer.commit = commit


def main(argv) -> int:
    import argparse
    from lib import harness
    from lib import manifest as mf
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    a = ap.parse_args(argv)
    m = mf.load()
    cell = mf.cell(m, a.workload)
    seconds = a.seconds if a.seconds is not None else m["run_seconds"]
    mix = mf.traffic_file(cell["traffic"])
    harness.require_chip(cell["chips"])
    for seed in (int(s) for s in a.seeds.split(",")):
        if mix["kind"] == "serve":
            from lib.serve import LIMITS
            got = serve_control(mf.config_file(m, cell["config"]), mix, seed,
                                seconds)
        else:
            res = ingest_control(a.workload, seed, seconds)
            got = {k: v["value"] for k, v in res["checks"].items()}
            from lib.ingest import LIMITS
        print(json.dumps({"workload": a.workload, "seed": seed,
                          "control": got, "limits": LIMITS,
                          "fails": any(got[k] > LIMITS[k] for k in LIMITS)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
