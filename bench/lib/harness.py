"""One run of one cell: device check, set-up, measured window, the
comparison that decides ``correct``, and the result line.

The cell's traffic file names its driver (``kind``): ``serve`` or
``ingest``, the modules ``lib/serve.py`` and ``lib/ingest.py``. A driver
offers ``setup()``, ``window(seconds, tracer)``, ``end_to_end()``,
``layer_context(summary, tracer)``, ``check()`` and ``close()``; the
harness does the rest. Every program runs on JAX's default device, the
cell's first chip.
"""
from __future__ import annotations

import importlib
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

from lib import manifest as mf
from lib.trace import TRACED_SPAN, breakdown, find_xplane, reduce


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_chip(chips: int):
    """The devices to run on; exits (no result line) without a TPU or
    with fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: no TPU found (JAX's first device is "
            f"{devs[0].platform!r}); this benchmark runs only on a TPU")
        raise SystemExit(3)
    if len(devs) < chips:
        log(f"bench: the cell needs {chips} chips, JAX sees {len(devs)}")
        raise SystemExit(3)
    return devs


class Tracer:
    """Profiler control for the driver: ``start()`` and ``stop()`` at the
    points of its window it chooses; both are no-ops unless tracing."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if enabled else None
        self.state = "idle"
        self._ann = None
        self.t_on = self.t_off = None

    def start(self) -> None:
        if not self.enabled or self.state != "idle":
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(TRACED_SPAN)
        self._ann.__enter__()
        self.t_on = time.perf_counter()
        self.state = "on"

    def stop(self) -> None:
        if self.state != "on":
            return
        import jax
        self.t_off = time.perf_counter()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def summary(self):
        if self.state != "done":
            return None
        import jax
        return reduce(jax.profiler.ProfileData.from_file(find_xplane(self.dir)))

    def close(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: Path = mf.ROOT, manifest: dict = None,
        check_chip: bool = True, overrides: dict = None,
        metrics_dir=None) -> dict:
    """Run the cell and return the result line's object. ``overrides``
    (``{"config": {...}, "traffic": {...}}``) and ``check_chip=False``
    let the tests drive a whole run at a size the CPU holds."""
    m = manifest if manifest is not None else mf.load(root / "BENCHMARK.json")
    cell = mf.cell(m, workload)
    overrides = overrides or {}
    cfg = _merge(mf.config_file(m, cell["config"], root),
                 overrides.get("config"))
    mix = _merge(mf.traffic_file(cell["traffic"], root / "bench"),
                 overrides.get("traffic"))
    import jax
    devs = require_chip(cell["chips"]) if check_chip else jax.devices()
    cache = "off"
    if check_chip:
        # $JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache
        from repro.launch.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
    from lib.compile_clock import CompileClock
    clock = CompileClock()
    dev = devs[0]
    log(f"device: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(devs)}; compile cache {cache}")
    log(f"cell {workload}: config {cell['config']}, traffic "
        f"{cell['traffic']}, seed {seed}, {seconds} s, trace {int(trace)}")
    driver = importlib.import_module(f"lib.{mix['kind']}").Driver(
        cfg, mix, seed, seconds)
    tracer = Tracer(trace)
    try:
        driver.setup()
        setup_s = time.perf_counter() - t_start
        log(f"set-up: {setup_s:.3f} s; {clock.line()}")
        programs0 = clock.programs()
        driver.window(float(seconds), tracer)
        driver.programs_in_window = clock.programs() - programs0
        log(f"window: {driver.programs_in_window} programs compiled or "
            f"loaded; {clock.line()}")
        stats = dev.memory_stats() or {}
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs[:cell["chips"]]) if stats else None
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": cell["chips"], "memory_peak_bytes": peak}
        metrics = {}
        extra = {}
        if trace:
            summary = tracer.summary()
            ctx = driver.layer_context(summary, tracer)
            if check_chip:
                from lib.peaks import chip_peaks
                ctx.peaks = chip_peaks(dev.device_kind)
            for x in mf.layer_of(m, workload):
                v = mf.metric_reader(x["name"], metrics_dir)(ctx)
                if v is not None:
                    metrics[x["name"]] = {"value": float(v), "unit": x["unit"]}
            if summary is not None:
                device["busy_s"] = summary.busy_s
                device["window_s"] = summary.window_s
                extra["breakdown"] = breakdown(summary)
        else:
            e2e = driver.end_to_end()
            e2e["setup_s"] = setup_s
            for x in mf.e2e_of(m, workload):
                metrics[x["name"]] = {"value": float(e2e[x["name"]]),
                                      "unit": x["unit"]}
        checks, attempted, failed = driver.check()
    finally:
        tracer.close()
        driver.close()
    correct = failed == 0 and all(v <= lim for v, lim in checks.values())
    for name, (v, lim) in checks.items():
        log(f"check {name}: {v!r} limit {lim!r}")
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device, **extra,
           "checks": {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}}
    return out


def main(argv, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    out = run(a.workload, a.seed, a.seconds, bool(a.trace), t_start=t_start)
    print(json.dumps(out), flush=True)
    return 0
