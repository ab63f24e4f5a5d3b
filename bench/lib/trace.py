"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

The traced part of a run is wrapped in one host annotation
(``TRACED_SPAN``); everything is measured inside it:

- ``busy_s``: the union of the intervals in which an operation runs on a
  device (the ``XLA Ops`` line of each ``/device:`` plane), averaged over
  the devices; ``window_s`` the span's length;
- ``op_seconds`` / ``module_seconds``: device time per operation and per
  program (``XLA Modules`` line), summed over devices;
- ``gaps``: the device's idle intervals, each named by the innermost
  host event on the span's thread that covers the gap's midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

TRACED_SPAN = "bench.traced"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    n_devices: int
    op_seconds: dict = field(default_factory=dict)
    module_seconds: dict = field(default_factory=dict)
    module_counts: dict = field(default_factory=dict)
    gaps: list = field(default_factory=list)

    def modules_matching(self, prefixes) -> tuple:
        """(seconds, count) of the programs whose name starts with one of
        ``prefixes``."""
        s = sum(v for k, v in self.module_seconds.items()
                if k.startswith(tuple(prefixes)))
        n = sum(v for k, v in self.module_counts.items()
                if k.startswith(tuple(prefixes)))
        return s, n

    def ops_matching(self, prefixes) -> float:
        """Device seconds of the operations whose HLO name (the text
        before `` = ``) starts with one of ``prefixes``."""
        return sum(v for k, v in self.op_seconds.items()
                   if k.split(" = ", 1)[0].startswith(tuple(prefixes)))


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(profile, top_gaps: int = 10) -> TraceSummary:
    """``profile`` is a ``jax.profiler.ProfileData``."""
    span = None
    host_lines = []
    device_planes = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            device_planes.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            host_lines.append(evs)
            for name, s, e in evs:
                if name == TRACED_SPAN:
                    span = (s, e, len(host_lines) - 1)
    if span is None:
        raise ValueError(f"the trace holds no {TRACED_SPAN!r} span")
    lo, hi, span_line = span
    op_seconds, module_seconds, module_counts = {}, {}, {}
    busy_per_device = []
    for plane in device_planes:
        ops = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if t <= lo or s >= hi:
                    continue
                d = (min(t, hi) - max(s, lo)) * 1e-9
                if line.name == OPS_LINE:
                    ops.append((s, t))
                    op_seconds[e.name] = op_seconds.get(e.name, 0.0) + d
                else:
                    module_seconds[e.name] = module_seconds.get(e.name, 0.0) + d
                    module_counts[e.name] = module_counts.get(e.name, 0) + 1
        if ops:
            busy_per_device.append(_clip(_union(ops), lo, hi))
    n_dev = len(busy_per_device)
    busy_s = (sum((e - s) for b in busy_per_device for s, e in b)
              * 1e-9 / n_dev) if n_dev else 0.0
    gaps = []
    if n_dev:
        edges = [lo] + [x for s, e in busy_per_device[0] for x in (s, e)] + [hi]
        host = [ev for ev in host_lines[span_line] if ev[0] != TRACED_SPAN]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            cover = [ev for ev in host if ev[1] <= mid <= ev[2]]
            name = min(cover, key=lambda ev: ev[2] - ev[1])[0] if cover \
                else "no host event"
            gaps.append((name, (e - s) * 1e-9))
        gaps.sort(key=lambda g: -g[1])
    return TraceSummary(window_s=(hi - lo) * 1e-9, busy_s=busy_s,
                        n_devices=n_dev, op_seconds=op_seconds,
                        module_seconds=module_seconds,
                        module_counts=module_counts, gaps=gaps[:top_gaps])


def op_label(name: str) -> str:
    """An operation's HLO text cut to its name, result type and opcode,
    layouts dropped: ``%fusion.7 = f32[1703936] fusion``."""
    lhs, _, rhs = name.partition(" = ")
    if not rhs:
        return name
    rhs = re.sub(r"\{[^{}]*\}", "", rhs)
    if rhs.startswith("("):
        depth = 0
        for j, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        typ, rest = rhs[:j + 1], rhs[j + 1:]
    else:
        typ, _, rest = rhs.partition(" ")
    return f"{lhs} = {typ} {rest.strip().split('(', 1)[0]}"


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    ops = sorted(summary.op_seconds.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[op_label(k), v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary.gaps[:top]]}
