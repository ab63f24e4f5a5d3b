"""The entry point's refusals, and a per-layer metric added as one new
file and found by name."""
import json
import os
import shutil
import subprocess
import sys

import bench_tiny
from lib import manifest as mf

RUN = [sys.executable, "bench/run.py", "--workload", "cw09b.ingest.bulk",
       "--seed", str(bench_tiny.SEED), "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(RUN, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=120)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(bench_tiny.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


def test_bare_benchmark_directory_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(os.path.join(bench_tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(bench_tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


def test_new_metric_file_is_found_by_name(tmp_path):
    metrics = tmp_path / "metrics"
    shutil.copytree(os.path.join(bench_tiny.BENCH, "metrics"), metrics,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (metrics / "zz.docs_fed.py").write_text(
        "def read(ctx):\n    return ctx.counters['tokens'] > 0\n")
    m = mf.load()
    m["per_layer"].append({"name": "zz.docs_fed", "unit": "flag",
                           "better": "higher", "source": "program_counter",
                           "layer": "codec and directory",
                           "moves": "ingest_docs_per_s",
                           "workloads": ["cw09b.ingest.bulk"]})
    out = bench_tiny.run_small("cw09b.ingest.bulk", trace=True, manifest=m,
                               metrics_dir=metrics)
    assert out["metrics"]["zz.docs_fed"] == {"value": 1.0, "unit": "flag"}
    assert "storage.write_amp" in out["metrics"]
    assert json.dumps(out)


def test_sweep_offers_each_rate_open_loop():
    """The sweep's open loop at test size: every arrival is served, and
    each row reports its rate, tail and backlog."""
    import sweep
    from lib import harness
    from lib.compile_clock import CompileClock
    from lib.serve import Driver
    m = mf.load()
    cfg = harness._merge(mf.config_file(m, "cw09b"), bench_tiny.SMALL_CONFIG)
    mix = {"kind": "serve", "loop": "open", "rate_qps": 40.0,
           "term_shares": {"2": 1, "3": 1, "4": 1}, "skip_top_terms": 33,
           "k": 10, "slots": 8, "max_terms": 4, "warm_seconds": 0.5,
           "trace_seconds": 1, "check_sample": 8}
    clock = CompileClock()
    driver = Driver(cfg, mix, bench_tiny.SEED, 1.0)
    driver.setup()
    rows = sweep.sweep(driver, [30.0, 60.0], 1.0, bench_tiny.SEED, clock)
    assert [r["rate_qps"] for r in rows] == [30.0, 60.0]
    assert [r["offered"] for r in rows] == [30, 60]
    assert all(r["p95_ms"] >= r["p50_ms"] > 0 for r in rows)
    assert all(q.done is not None for q in driver.requests)
