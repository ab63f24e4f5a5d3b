"""Shared set-up of the benchmark's CPU tests: import paths, and the
overrides that shrink a cell to what a test run holds (the same code
paths, at a few thousand short documents over a 2^14-term vocabulary)."""
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL_CONFIG = {"n_docs": 4096,
                "corpus": {"doc_len": 128, "vocab_bits": 14,
                           "median_doc_len": 48},
                "index": {"docs_per_shard": 512, "doc_len": 128,
                          "vocab_bits": 14, "flush_budget_mb": 1}}
SMALL_TRAFFIC = {
    "cw09b.ingest.bulk": {"batch_docs": 512, "commit_every_batches": 2,
                          "ahead_docs_per_s": 40000, "group_s": 1},
    "cw09b.batch.k1000": {"warm_batches": 1, "trace_seconds": 1, "k": 100,
                          "check_sample": 8},
}
SEED = 2 ** 31 + 12345     # past 32 signed bits, as the driver's seeds are


def run_small(workload: str, seconds: float = 1.0, trace: bool = False,
              **kw) -> dict:
    """A whole run of ``workload`` at test size, without the chip check."""
    from lib import harness
    return harness.run(workload, SEED, seconds, trace,
                       t_start=time.perf_counter(), check_chip=False,
                       overrides={"config": SMALL_CONFIG,
                                  "traffic": SMALL_TRAFFIC[workload]}, **kw)
