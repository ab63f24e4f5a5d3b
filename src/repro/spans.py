"""Named host spans of the ingest and serving paths.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation``: the span
lands on the host plane of the profiler's trace, on the clock of the
device's events, with ``ids`` as the event's stats. The profiler buffers
spans and writes them with the trace; with no trace running a span costs
about a microsecond, so spans carry no switch.

``SPANS`` names every span the program opens, with what it covers. A
leaf is a span inside which no other span of the table opens.
"""
from __future__ import annotations

import jax

SPANS = {
    # ingest: DistributedIndexer.commit -> flush -> SegmentStore.commit
    "indexer.commit": "DistributedIndexer.commit: flush, then publish a commit point",
    "indexer.flush": "DistributedIndexer._flush_locked: the token buffer into one segment",
    "flush.to_device": "leaf: the flush's tokens copied to the device",
    "flush.invert": "leaf: the jitted inversion, run to completion",
    "flush.to_host": "leaf: the inverted run's arrays copied back to the host",
    "flush.segment": "leaf: segment_from_run, and BP doc-id reassignment when on",
    "flush.account": "leaf: the flushed segment's modelled index bytes (merge accounting)",
    "codec.encode": "leaf: encode_segment, a segment's files built in memory",
    "directory.write": "leaf: Directory.write_file",
    "directory.sync": "leaf: Directory.sync",
    "store.commit": "SegmentStore.commit: .liv files, manifest, sync, deletes",
    # serving: QueryScheduler.step -> IndexSearcher._search_pruned
    "sched.step": "QueryScheduler.step: one batch from the queue to its results",
    "search.plan": "leaf: global idf, per-segment score bounds, visit order",
    "search.segment": "one visited segment of the pruned search",
    "prune.meta": "leaf: the metadata pass, run to completion on the device (no fetch)",
    "prune.probe": "leaf: the phase-1 probe on the device: pick, scorer, run to completion (no fetch)",
    "prune.bound": "leaf: the device bound test, term elimination and probe keep, and the fetch of its counts",
    "prune.compact": "leaf: compact_survivors, the device gather of the survivors (launch)",
    "score.survivors": "leaf: the survivor scorer (or midgrid) and its fetch",
    "search.merge": "leaf: the cross-segment top-k and its fetch",
}


def span(name: str, **ids):
    """A span of the table, with ``ids`` recorded as its event's stats."""
    assert name in SPANS, name
    return jax.profiler.TraceAnnotation(name, **ids)
