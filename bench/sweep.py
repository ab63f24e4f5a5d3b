#!/usr/bin/env python3
"""Highest rate an open-loop serving cell sustains, by a sweep on the chip.

    python3 bench/sweep.py --config cw09b --traffic <open-loop mix> \
        --seed <n> --seconds 51 --rates 58,52,46,40,34

Sets the cell up once (its warm-up included), then offers each rate in
turn for ``--seconds`` with fresh queries, and prints one row per rate:
offered and completed queries per second, p50 and p95 latency from the
intended arrival, the backlog at the window's close (requests due by
then and not yet taken by a step), and the programs compiled or loaded
during it. A rate is sustained when that backlog is at most one batch
(``slots``). The cell's traffic file then fixes its rate at 0.8 x the
highest sustained rate. Runs only on a TPU, like ``run.py``.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def sweep(driver, rates, seconds: float, seed: int, clock) -> list:
    """One row per rate over an already set-up serving ``driver``."""
    import numpy as np
    from lib.serve import _step_line
    rows = []
    for j, rate in enumerate(rates):
        driver.mix = dict(driver.mix, rate_qps=float(rate))
        programs = clock.programs()
        driver._open_loop(seconds, np.random.default_rng((seed, 100 + j)),
                          None)
        programs = clock.programs() - programs
        reqs = driver.requests
        lat = np.array([(r.done - r.due) * 1e3 if r.done is not None
                        else np.inf for r in reqs])
        close = driver.t_close
        backlog = sum(1 for r in reqs if r.due <= close
                      and (r.launch is None or r.launch > close))
        done = sum(1 for r in reqs if r.done is not None and r.done <= close)
        rows.append({"rate_qps": float(rate), "offered": len(reqs),
                     "completed_per_s": done / seconds,
                     "p50_ms": float(np.percentile(lat, 50)),
                     "p95_ms": float(np.percentile(lat, 95)),
                     "backlog": backlog,
                     "sustained": backlog <= driver.mix["slots"],
                     "programs_in_window": programs,
                     "steps": _step_line(driver.steps)})
    return rows


def main(argv) -> int:
    import argparse
    import json
    from lib import harness
    from lib import manifest as mf
    from lib.compile_clock import CompileClock
    from lib.serve import Driver
    from repro.launch.compile_cache import enable_compile_cache
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="cw09b")
    ap.add_argument("--traffic", required=True,
                    help="an open-loop serving mix of bench/traffic")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, queries/s")
    a = ap.parse_args(argv)
    mix = mf.traffic_file(a.traffic)
    if mix.get("loop") != "open":
        raise SystemExit(f"{a.traffic} is not an open-loop serving mix")
    devs = harness.require_chip(1)
    enable_compile_cache()
    clock = CompileClock()
    driver = Driver(mf.config_file(mf.load(), a.config), mix, a.seed,
                    a.seconds)
    driver.setup()
    harness.log(f"set-up: {time.perf_counter() - T_START:.1f} s; "
                f"{clock.line()}")
    rows = sweep(driver, [float(r) for r in a.rates.split(",")],
                 a.seconds, a.seed, clock)
    for r in rows:
        harness.log(" ".join(f"{k} {v}" for k, v in r.items()))
    best = max((r["rate_qps"] for r in rows if r["sustained"]), default=None)
    print(json.dumps({"config": a.config, "traffic": a.traffic, "rows": rows,
                      "highest_sustained_qps": best,
                      "device": {"platform": devs[0].platform,
                                 "kind": devs[0].device_kind}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
