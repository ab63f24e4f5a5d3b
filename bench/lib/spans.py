"""Self time of the program's own host spans in a run's trace.

The program opens named spans at the stage boundaries of its ingest and
serving paths (the table ``SPANS`` of ``repro/spans.py``, whose names
``SPAN_NAMES`` repeats); the profiler writes them on the host plane, on
the clock of the device's events. A span's self time is its duration,
clipped to the traced span, less what the program's spans nested in it
on the same thread cover. JAX's own host events (dispatch, transfers)
count toward the program span around them.

The readers' ``Context`` carries the trace's summary, not its host
events, so ``of_run`` reads the trace file of the run whose readers it
serves: the harness's tracer (the ``tracer`` of ``harness.run``) keeps
that file until the readers are done. This stands in for span times in
``lib.trace.reduce()``, which the benchmark's trace reduction does not
give yet. A program that opens none of these spans gives no times, and
each reader then reports nothing.
"""
from __future__ import annotations

import functools
import os
import sys
from dataclasses import dataclass

from lib.trace import TRACED_SPAN, find_xplane

SPAN_NAMES = (
    "indexer.commit", "indexer.flush", "flush.to_device", "flush.invert",
    "flush.to_host", "flush.segment", "flush.account", "codec.encode",
    "directory.write", "directory.sync", "store.commit",
    "sched.step", "search.plan", "search.segment", "prune.meta",
    "prune.probe", "prune.bound", "prune.compact", "score.survivors",
    "search.merge",
)


@dataclass
class SpanTime:
    self_s: float = 0.0     # duration less the program spans nested in it
    total_s: float = 0.0    # duration, clipped to the traced span
    count: int = 0


def span_times(profile, names=SPAN_NAMES) -> dict:
    """``{name: SpanTime}`` over every host line of ``profile`` (a
    ``jax.profiler.ProfileData``), inside its ``TRACED_SPAN``; names that
    never occur are absent."""
    lines, window = [], None
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = []
            for e in line.events:
                if e.name == TRACED_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name in names:
                    evs.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
            lines.append(evs)
    if window is None:
        raise ValueError(f"the trace holds no {TRACED_SPAN!r} span")
    lo, hi = window
    out = {}
    for evs in lines:
        stack = []      # open ancestors: (end, SpanTime)
        for s, e, name in sorted(evs, key=lambda ev: (ev[0], -ev[1])):
            while stack and stack[-1][0] <= s:
                stack.pop()
            d = max(0, min(e, hi) - max(s, lo)) * 1e-9
            t = out.setdefault(name, SpanTime())
            t.self_s += d
            t.total_s += d
            t.count += d > 0
            if stack:
                stack[-1][1].self_s -= d
            stack.append((e, t))
    return {k: v for k, v in out.items() if v.count}


def of_run(ctx):
    """Span times of the traced run whose metric readers are being
    called, or None without a trace. Raises when the run was traced but
    no finished tracer is found among the callers' locals, so that a
    change to the harness fails loudly rather than dropping every span
    metric."""
    if ctx.trace is None:
        return None
    frame = sys._getframe(1)
    while frame is not None:
        tracer = frame.f_locals.get("tracer")
        if getattr(tracer, "state", None) == "done" and tracer.dir:
            break
        frame = frame.f_back
    else:
        raise RuntimeError("a traced run's readers were called, but no "
                           "finished `tracer` is among their callers' "
                           "locals (harness.run)")
    path = find_xplane(tracer.dir)
    st = os.stat(path)
    return _of_file(path, st.st_mtime_ns, st.st_size)


@functools.lru_cache(maxsize=1)
def _of_file(path, mtime_ns, size):
    """One parse of a trace file for all the readers of its run."""
    import jax
    return span_times(jax.profiler.ProfileData.from_file(path))


def self_s(ctx, names):
    """Summed self time of ``names`` in the traced run, None when none of
    them was opened there."""
    times = of_run(ctx)
    if not times or not any(n in times for n in names):
        return None
    return sum(times[n].self_s for n in names if n in times)


def ms_per_kdoc(ctx, names):
    """Self time of ``names`` per thousand documents flushed in the
    traced span, in ms."""
    s, docs = self_s(ctx, names), ctx.counters.get("traced_docs")
    return None if s is None or not docs else 1e3 * s / (docs / 1e3)


def ms_per_batch(ctx, names):
    """Self time of ``names`` per batch served in the traced span, in
    ms."""
    s, n = self_s(ctx, names), ctx.counters.get("traced_batches")
    return None if s is None or not n else 1e3 * s / n
