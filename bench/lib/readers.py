"""What a per-layer metric reader gets (``Context``), and the arithmetic
that several readers share. A reader returns None when its cell gave it
nothing to read; the harness then leaves the metric out."""
from __future__ import annotations

from dataclasses import dataclass, field

# Names the program gives its device work, as the trace shows them: the
# jitted survivor scorers of ``core/searcher.py`` (``score``: masked and
# midgrid; ``nolive``: tombstone-free), the Pallas scoring kernels of
# ``kernels/bm25_blockmax`` and the jitted ``core/invert.invert_shard``.
SCORER_PROGRAMS = ("jit_score", "jit_nolive")
KERNEL_OPS = ("%bm25_blocks_pallas", "%bm25_blocks_midgrid_pallas",
              "%bm25_blocks_compact_pallas")
INVERT_PROGRAMS = ("jit_invert_shard",)


@dataclass
class Context:
    window_s: float              # the measured window, seconds
    trace: object = None         # lib.trace.TraceSummary of the traced span
    counters: dict = field(default_factory=dict)   # window deltas
    queue_ms: list = field(default_factory=list)   # serve: due -> launch
    commit_s: list = field(default_factory=list)   # ingest: commit spans
    block_bytes: float = None    # serve: mean compressed bytes per block
    peaks: dict = None           # the chip's row of lib/peaks.py


def idle_share(ctx: Context):
    """Percent of the traced span in which no operation ran on the
    device."""
    t = ctx.trace
    if t is None or t.n_devices == 0 or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


