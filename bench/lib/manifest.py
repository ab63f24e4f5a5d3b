"""``BENCHMARK.json``: loading, validation and lookup by name.

Every configuration, traffic mix and per-layer metric is a file of its
own under ``bench/``, found by the name the manifest gives it:

    bench/configs/<config>.json     sizes and guarantees of a deployment
    bench/traffic/<traffic>.json    parameters read by the one generator
    bench/metrics/<metric>.py       a reader with ``read(ctx)``

so a later change adds a cell, a mix or a metric by adding files and
entries, never by editing one that is there.
"""
from __future__ import annotations

import importlib.util
import json
import math
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
E2E_SOURCES = {"host_clock", "device_trace"}
SOURCES = E2E_SOURCES | {"program_span", "program_counter"}


def load(path=None) -> dict:
    path = Path(path) if path is not None else ROOT / "BENCHMARK.json"
    return json.loads(path.read_text())


def _line(text, what, errors):
    if not isinstance(text, str) or not 1 <= len(text) <= 200 \
            or "\n" in text or "\t" in text:
        errors.append(f"{what}: 1 to 200 characters on one line, no tab")


def validate(m: dict, bench_dir=None) -> list:
    """Every rule of the benchmark's contract that the file alone can
    show; returns the faults found (empty when the manifest is sound)."""
    bench_dir = Path(bench_dir) if bench_dir is not None else BENCH
    errors = []
    if set(m) != TOP_KEYS:
        errors.append(f"top-level keys {sorted(m)} are not {sorted(TOP_KEYS)}")
        return errors
    cmd = m["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        errors.append("command: a list of 1 to 32 strings")
    else:
        for w in cmd:
            _line(w, f"command word {w!r}", errors)
            if isinstance(w, str) and (w.startswith("/") or ".." in w):
                errors.append(f"command word {w!r} leaves the checkout")
    paths = m["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        errors.append("paths: 1 to 16 directories")
        paths = []
    for p in paths:
        if not PATH_RE.match(p) or p.startswith("/") or ".." in p:
            errors.append(f"path {p!r}: relative, at most 200 of "
                          f"[A-Za-z0-9_./-]")
    for w in cmd[1:] if isinstance(cmd, list) else ():
        if "/" in w and not any(w.startswith(p.rstrip("/") + "/")
                                for p in paths):
            errors.append(f"command names {w!r} outside paths")
    rs = m["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        errors.append("run_seconds: a whole number from 1 to 51")

    def named(kind, items, keys, lo, hi, optional=()):
        if not (isinstance(items, list) and lo <= len(items) <= hi):
            errors.append(f"{kind}: {lo} to {hi} entries")
            return {}
        out = {}
        for it in items:
            if not keys <= set(it) or set(it) - keys - set(optional):
                errors.append(f"{kind} entry {it.get('name')!r}: keys "
                              f"{sorted(it)} are not {sorted(keys)}")
            n = it.get("name", "")
            if not isinstance(n, str) or not NAME_RE.match(n):
                errors.append(f"{kind} name {n!r} breaks the name rule")
            if n in out:
                errors.append(f"{kind} name {n!r} appears twice")
            out[n] = it
        return out

    configs = named("configs", m["configs"], CONFIG_KEYS, 1, 24)
    for c in configs.values():
        _line(c.get("source"), f"config {c['name']} source", errors)
        _line(c.get("why"), f"config {c['name']} why", errors)
        red = c.get("reduced", [])
        if not isinstance(red, list) or len(red) > 16 or \
                not all(isinstance(k, str) and NAME_RE.match(k) for k in red):
            errors.append(f"config {c['name']}: reduced is a list of at "
                          f"most 16 names")
        f = c.get("file", "")
        if not any(f.startswith(p.rstrip("/") + "/") for p in paths):
            errors.append(f"config {c['name']}: file {f!r} not under paths")
        elif not (bench_dir.parent / f).is_file():
            errors.append(f"config {c['name']}: file {f!r} missing")
    files = [c.get("file") for c in configs.values()]
    if len(set(files)) != len(files):
        errors.append("two configurations share a file")

    cells = named("workloads", m["workloads"], WORKLOAD_KEYS, 1, 24)
    pairs = set()
    for w in cells.values():
        _line(w.get("why"), f"workload {w['name']} why", errors)
        if w.get("config") not in configs:
            errors.append(f"workload {w['name']}: no config "
                          f"{w.get('config')!r}")
        t = w.get("traffic", "")
        if not isinstance(t, str) or not NAME_RE.match(t):
            errors.append(f"workload {w['name']}: traffic name {t!r}")
        elif not (bench_dir / "traffic" / f"{t}.json").is_file():
            errors.append(f"workload {w['name']}: no traffic file for {t!r}")
        if w.get("chips") not in (1, 4):
            errors.append(f"workload {w['name']}: chips is 1 or 4")
        if (w.get("config"), t) in pairs:
            errors.append(f"workload {w['name']}: config and traffic pair "
                          f"appears twice")
        pairs.add((w.get("config"), t))
    four = sum(1 for w in cells.values() if w.get("chips") == 4)
    if four > max(1, len(cells) // 2):
        errors.append(f"{four} of {len(cells)} cells ask for 4 chips")
    used = {w.get("config") for w in cells.values()}
    for c in configs:
        if c not in used:
            errors.append(f"config {c!r} is used by no cell")

    e2e = named("end_to_end", m["end_to_end"], E2E_KEYS, 1, 16,
                ("workloads",))
    layer = named("per_layer", m["per_layer"], LAYER_KEYS, 1, 128,
                  ("workloads",))
    if set(e2e) & set(layer):
        errors.append(f"metric names shared: {sorted(set(e2e) & set(layer))}")
    for kind, metrics in (("end_to_end", e2e), ("per_layer", layer)):
        for x in metrics.values():
            if not UNIT_RE.match(str(x.get("unit", ""))):
                errors.append(f"metric {x['name']}: unit {x.get('unit')!r}")
            if x.get("better") not in ("lower", "higher"):
                errors.append(f"metric {x['name']}: better is lower/higher")
            src = x.get("source")
            if src not in (E2E_SOURCES if kind == "end_to_end" else SOURCES):
                errors.append(f"metric {x['name']}: source {src!r}")
            for wl in x.get("workloads", []):
                if wl not in cells:
                    errors.append(f"metric {x['name']}: no cell {wl!r}")
    if "setup_s" not in e2e:
        errors.append("end_to_end lacks setup_s")
    for x in e2e.values():
        b = x.get("bound")
        if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.25
                and math.isfinite(b)):
            errors.append(f"metric {x['name']}: bound {b!r} outside "
                          f"[0.01, 0.25]")
    for x in layer.values():
        _line(x.get("layer"), f"metric {x['name']} layer", errors)
        mv = x.get("moves")
        if mv not in e2e:
            errors.append(f"metric {x['name']}: moves {mv!r} is no "
                          f"end-to-end metric")
            continue
        for wl in x.get("workloads", list(cells)):
            if wl in cells and mv not in [e["name"] for e in e2e_of(m, wl)]:
                errors.append(f"metric {x['name']}: cell {wl} does not "
                              f"report {mv}")
        if x["name"].endswith("_roofline") and x.get("unit") != "%":
            errors.append(f"metric {x['name']}: a roofline share is in %")
        if not (bench_dir / "metrics" / f"{x['name']}.py").is_file():
            errors.append(f"metric {x['name']}: no reader file")
    for wl in cells:
        if len(e2e_of(m, wl)) < 2:
            errors.append(f"cell {wl}: reports no end-to-end metric "
                          f"besides setup_s")
        if not layer_of(m, wl):
            errors.append(f"cell {wl}: reports no per-layer metric")
    if len(json.dumps(m).encode()) > 64 * 1024:
        errors.append("manifest over 64 KiB")
    return errors


def e2e_of(m: dict, workload: str) -> list:
    """End-to-end metric entries the cell reports."""
    return [x for x in m["end_to_end"]
            if workload in x.get("workloads", [workload])]


def layer_of(m: dict, workload: str) -> list:
    """Per-layer metric entries the cell reports."""
    return [x for x in m["per_layer"]
            if workload in x.get("workloads", [workload])]


def cell(m: dict, workload: str) -> dict:
    for w in m["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in m['workloads']]}")


def config_file(m: dict, name: str, root=None) -> dict:
    root = Path(root) if root is not None else ROOT
    for c in m["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no config {name!r}")


def traffic_file(name: str, bench_dir=None) -> dict:
    bench_dir = Path(bench_dir) if bench_dir is not None else BENCH
    return json.loads((bench_dir / "traffic" / f"{name}.json").read_text())


def metric_reader(name: str, metrics_dir=None):
    """The ``read(ctx)`` function of ``<metrics_dir>/<name>.py``."""
    metrics_dir = Path(metrics_dir) if metrics_dir is not None \
        else BENCH / "metrics"
    path = metrics_dir / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
