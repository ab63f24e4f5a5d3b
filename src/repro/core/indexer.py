"""End-to-end distributed indexing: the paper's pipeline as one SPMD step
plus a host-side flush/merge driver with envelope accounting.

Device step (jit + shard_map over the production mesh):
  tokenized doc buffers (sharded over every mesh axis)
    -> per-device lexicographic sort inversion        (core.invert)
    -> all-to-all term shuffle over ``model``         (core.shuffle)
    -> term-sharded postings + lane-blocked PFor pack (kernels.postings_pack)

Host driver (DistributedIndexer): accumulates flushed runs into Segments,
feeds the tiered MergeDriver (write amplification alpha is *measured*),
and charges bytes to the source/target media models (core.envelope) to
produce the predicted wall-clock an equivalent CPU server would need —
reproducing the paper's Table 1 protocol on our own pipeline.

Read path: ``refresh()`` snapshots the live segment set into an
``IndexSearcher`` (core.searcher) *without* force-merging — near-real-time
search-while-indexing. Per-segment readers are cached across refreshes
keyed by segment identity, so a refresh after a merge cascade only builds
a reader for the cascade's output. ``finalize()`` remains the paper's
force-merged end state.

Document lifecycle: ``delete(doc_ids)`` tombstones docs and
``update(doc_id, doc)`` is delete + re-add under the flush lock (doc-id
allocation unchanged — the replacement content gets a fresh id at flush).
Deletes are buffered like Lucene's BufferedUpdates and folded into the
live segment set at the next flush/refresh/commit, so every snapshot
taken after the call returns excludes the docs; tombstoned postings are
physically dropped by merges (core.merge) and the bitmaps become durable
``.liv`` generation files at ``commit()`` (repro.storage). With
``refresh_every > 0`` a daemon thread refreshes ``self.searcher``
periodically (the swap is a single attribute store, already atomic) and
is stopped/joined by ``close()``.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import envelope as env
from repro.core.invert import invert_shard
from repro.core.merge import (ConcurrentMergeScheduler, MergeDriver,
                              reassign_doc_ids)
from repro.core.searcher import IndexSearcher, ReaderCache
from repro.core.segments import Segment, segment_from_run
from repro.core.shuffle import invert_and_shuffle
from repro.kernels.postings_pack import ref as pack_ref


def _flat_device_index(mesh_axis_names, mesh_shape):
    """Flattened linear device index inside shard_map, row-major over
    ``mesh_axis_names`` with sizes from the (static) mesh shape."""
    idx = jnp.int32(0)
    for name in mesh_axis_names:
        idx = idx * mesh_shape[name] + lax.axis_index(name)
    return idx


def make_index_step(cfg, mesh, doc_len: int):
    """Returns the jitted-lowerable SPMD indexing step.

    tokens: (D_global, doc_len) int32 sharded over every mesh axis.
    Outputs: per-device InvertedRun (term-sharded), packed doc-delta and
    position-delta blocks, shuffle stats, byte counters.
    """
    axis_names = tuple(mesh.axis_names)
    n_model = mesh.shape["model"]

    payload = getattr(cfg, "shuffle_payload", "raw")
    single_key = payload == "packed2"  # optimized variant bundles both

    mesh_shape = dict(mesh.shape)

    def local_fn(toks):
        dev = _flat_device_index(axis_names, mesh_shape)
        base = dev * toks.shape[0]
        run, stats = invert_and_shuffle(toks, base, axis_name="model",
                                        n_dest=n_model, payload=payload,
                                        single_key_sort=single_key)
        nb = run.postings_doc_delta.shape[0] // pack_ref.BLOCK
        dd = run.postings_doc_delta[:nb * pack_ref.BLOCK]
        packed_d, bw_d = pack_ref.pack_ref(
            dd.reshape(nb, pack_ref.BLOCK).astype(jnp.uint32))
        pb = run.pos_delta.shape[0] // pack_ref.BLOCK
        pd = run.pos_delta[:pb * pack_ref.BLOCK]
        packed_p, bw_p = pack_ref.pack_ref(
            pd.reshape(pb, pack_ref.BLOCK).astype(jnp.uint32))
        written = pack_ref.packed_bytes(bw_d) + pack_ref.packed_bytes(bw_p)
        out = {
            "run": run, "stats": stats,
            "packed_docs": packed_d, "bw_docs": bw_d,
            "packed_pos": packed_p, "bw_pos": bw_p,
            "packed_bytes": written,
        }
        return jax.tree.map(lambda x: x[None] if x.ndim == 0 else x, out)

    full_spec = P(axis_names if len(axis_names) > 1 else axis_names[0], None)

    def step(tokens):
        return shard_map(local_fn, mesh=mesh, in_specs=full_spec,
                         out_specs=P(axis_names[0] if len(axis_names) == 1
                                     else axis_names),
                         check_vma=False)(tokens)

    return step


@dataclass
class IndexStats:
    docs: int = 0
    tokens: int = 0
    read_bytes: int = 0
    flushed_bytes: int = 0
    shuffle_bytes: int = 0
    wall_s: float = 0.0
    refreshes: int = 0
    last_refresh_s: float = 0.0
    deletes: int = 0    # acknowledged delete ids (incl. updates' deletes)
    updates: int = 0


@dataclass
class DistributedIndexer:
    """Host driver: jit step + flush/merge + envelope accounting.

    Single-process version (mesh=None) runs the same pipeline with one
    device shard — used by tests, examples and the benchmark harness.
    """

    cfg: object
    source: str = "ceph"
    target: str = "ssd"
    mesh: object = None
    media: dict = None
    params: env.EnvelopeParams = None
    stats: IndexStats = field(default_factory=IndexStats)
    merger: MergeDriver = None
    reader_cache: ReaderCache = None
    # durable storage (repro.storage): when target_dir is set, every
    # flushed/merged segment is encoded through it (storage/codec) and
    # ``commit()`` publishes durable commit points; constructing over a
    # non-empty directory RESUMES from its latest commit (recovery).
    # source_dir streams the spooled source collection (index_spooled), so
    # source and target IO are measured on physically separate Directories.
    target_dir: object = None
    source_dir: object = None
    store: object = None
    # > 0: run merges on a ConcurrentMergeScheduler with that many worker
    # threads, so index_batch/_flush never wait on a cascade. 0: synchronous
    # merges inside add_flush, the paper's coupled write path. None
    # (default): take cfg.merge_threads (an explicit 0 here overrides a
    # concurrent config).
    merge_threads: int = None
    merge_scheduler: ConcurrentMergeScheduler = None
    # > 0: cap background-merge IO at this MB/s (Lucene's ioThrottle) so
    # cascades never monopolize the target medium against flushes. None:
    # take cfg.merge_io_mbps; 0 disables.
    merge_io_mbps: float = None
    # > 0: a daemon thread refreshes ``self.searcher`` every this many
    # seconds (NRT reader polling); the swap is a plain attribute store,
    # so serving threads just read ``indexer.searcher``. None: take
    # cfg.refresh_every; 0 disables. Stopped and joined by ``close()``.
    refresh_every: float = None
    searcher: IndexSearcher = None   # latest refreshed snapshot
    # ---- fault tolerance (repro.storage fault layer) ----
    # wal=True: every acked add/delete is frame-logged + synced to a
    # ``wal_N`` file BEFORE index_batch/delete returns, replayed on
    # recovery and truncated at commit — kill -9 between ack and flush
    # loses nothing. None: take cfg.wal (default off). Needs target_dir.
    wal: bool = None
    # wal_group=True: group commit — the record is appended under the
    # flush lock but the sync barrier runs OUTSIDE it, and concurrent
    # ackers coalesce into one batched ``directory.sync`` (a leader syncs
    # the whole unsynced tail; see ``WriteAheadLog.sync_upto``). Acks
    # still block until their record is durable, so kill -9 after an ack
    # loses nothing — the fsync cost is amortized over the group. None:
    # take cfg.wal_group (default off: one fsync per ack, the strictest
    # failure accounting).
    wal_group: bool = None
    # a storage.RetryPolicy: target_dir is wrapped in a RetryingDirectory
    # so every op under SegmentStore / write_commit / .liv writes retries
    # transient faults with capped backoff (persistent ones propagate
    # typed). None: no wrapping (callers may stack their own).
    retry_policy: object = None
    # > 0: background merges that fault are re-enqueued with backoff up
    # to this many times (ConcurrentMergeScheduler retry) before a typed
    # MergeRetriesExhausted parks. None: take cfg.merge_retries; 0 keeps
    # the park-on-first-failure behavior.
    merge_retries: int = None
    # > 0: a ChecksumScrubber daemon re-verifies committed frames every
    # this many seconds (scrub_io_mbps caps its read rate via a
    # MergeRateLimiter), feeding detections into store quarantine. The
    # scrubber object exists (for manual ``sweep()``) whenever target_dir
    # is set. None: take cfg.scrub_every / cfg.scrub_io_mbps.
    scrub_every: float = None
    scrub_io_mbps: float = None
    # recover a partially-corrupt newest commit minus its quarantined
    # segments (degraded) instead of falling back / failing
    degraded_ok: bool = False
    scrubber: object = None
    # ---- fleet serving (repro.replication) ----
    # a replication.CommitPublisher: every durable commit is announced to
    # it (``on_commit``), and ``envelope_report`` grows a ``fleet``
    # section with the per-replica lag/bytes ledger its acks feed.
    publisher: object = None
    # first doc id this writer allocates (doc-range sharding: shard i of
    # a fleet runs its own writer with doc_base = i * range_size, keeping
    # global doc-id spaces disjoint across shards). Recovery resumes from
    # max(committed max + 1, doc_base).
    doc_base: int = 0
    # ---- steady-state serving (repro.serving) ----
    # callables invoked with the fresh searcher after every ``refresh``
    # swap — ``attach_serving`` registers the scheduler's
    # ``swap_searcher`` here, so refresh -> generation bump -> exact
    # result-cache invalidation is one wiring call.
    on_refresh: list = None
    serving: object = None       # attached QueryScheduler (report source)
    _postings_cache: object = None   # CachingDirectory when configured
    _next_doc: int = 0
    _wal: object = None
    _wal_covered: int = -1     # highest wal seq whose ops are flushed
    _wal_replaying: bool = False

    def __post_init__(self):
        from repro.core.flush import FlushPolicy
        self.media = self.media or env.MEDIA
        self.params = self.params or env.EnvelopeParams()
        self.merger = MergeDriver(
            fanout=self.cfg.merge_fanout,
            reorder_on_merge=getattr(self.cfg, "reorder_on_merge", False))
        if self.on_refresh is None:
            self.on_refresh = []
        if self.retry_policy is not None and self.target_dir is not None:
            from repro.storage.retry import RetryingDirectory
            if not isinstance(self.target_dir, RetryingDirectory):
                self.target_dir = RetryingDirectory(self.target_dir,
                                                    self.retry_policy)
        # hot-term postings cache ABOVE the whole media stack (retry /
        # faults / throttle): repeat reads of head-term segment files stop
        # paying media latency. Everything below still sees real IO, and
        # the scrubber deliberately reads the unwrapped stack so cached
        # blocks can't mask on-media bit rot.
        cache_mb = float(getattr(self.cfg, "postings_cache_mb", 0.0) or 0.0)
        if cache_mb > 0 and self.target_dir is not None:
            from repro.storage.directory import CachingDirectory
            self.target_dir = CachingDirectory(
                self.target_dir, cap_bytes=int(cache_mb * 1e6))
            self._postings_cache = self.target_dir
        if self.target_dir is not None:
            from repro.storage.commit import SegmentStore
            self.store, recovered = SegmentStore.open(
                self.target_dir, codec=getattr(self.cfg, "codec", "pfor"),
                degraded=self.degraded_ok)
            self.merger.store = self.store
            # resume from the last commit point: recovered segments rejoin
            # their merge tier, new doc ids continue after the committed
            # max. Their bytes are credited as prior writes (the original
            # run's merge history is gone, so the floor is one write each:
            # alpha restarts at ~1 for recovered data and grows with new
            # work, instead of dipping below 1).
            for seg in recovered:
                sz = seg.total_bytes()
                self.merger.bytes_written += sz
                self.merger.flushed_bytes += sz
                self.merger.tiers.setdefault(seg.generation, []).append(seg)
            tops = [int(s.doc_ids.max()) for s in recovered if s.n_docs]
            if tops:
                self._next_doc = max(tops) + 1
        self._next_doc = max(self._next_doc, self.doc_base)
        if self.merge_threads is None:
            self.merge_threads = self.cfg.merge_threads
        if self.merge_retries is None:
            self.merge_retries = getattr(self.cfg, "merge_retries", 0)
        if self.merge_threads:
            merge_policy = None
            if self.merge_retries:
                from repro.storage.retry import RetryPolicy
                merge_policy = RetryPolicy(max_retries=self.merge_retries,
                                           base_delay_s=0.01,
                                           max_delay_s=0.25)
            self.merge_scheduler = ConcurrentMergeScheduler(
                self.merger, max_threads=self.merge_threads,
                retry_policy=merge_policy)
        if self.merge_io_mbps is None:
            self.merge_io_mbps = getattr(self.cfg, "merge_io_mbps", 0.0)
        if self.merge_io_mbps:
            from repro.core.merge import MergeRateLimiter
            self.merger.io_limiter = MergeRateLimiter(self.merge_io_mbps)
        self.reader_cache = ReaderCache()
        self._flush_policy = FlushPolicy(budget_mb=self.cfg.flush_budget_mb)
        # serializes the flush buffer handoff + doc-id allocation: refresh
        # (flush=True) may be called from a search thread while the ingest
        # thread is mid-index_batch, and overlapping doc-id ranges would
        # break the disjointness invariant the merge path asserts on
        self._flush_lock = threading.RLock()
        self._jit_invert = jax.jit(invert_shard)
        # document lifecycle: acknowledged-but-unapplied delete ids
        # (Lucene's BufferedUpdates), drained at flush under _flush_lock
        self._buffered_deletes = np.zeros(0, np.int64)
        if self.wal is None:
            self.wal = bool(getattr(self.cfg, "wal", False))
        if self.wal_group is None:
            self.wal_group = bool(getattr(self.cfg, "wal_group", False))
        if self.wal and self.target_dir is not None:
            from repro.storage.wal import WriteAheadLog
            self._wal = WriteAheadLog(
                self.target_dir,
                rotate_bytes=int(float(getattr(self.cfg, "wal_rotate_mb",
                                               0.0) or 0.0) * 1e6),
                recycle_keep=int(getattr(self.cfg, "wal_recycle", 0) or 0))
            self._wal_covered = -1
            self._replay_wal()
        if self.scrub_every is None:
            self.scrub_every = getattr(self.cfg, "scrub_every", 0.0)
        if self.scrub_io_mbps is None:
            self.scrub_io_mbps = getattr(self.cfg, "scrub_io_mbps", 0.0)
        if self.target_dir is not None:
            from repro.core.merge import MergeRateLimiter
            from repro.storage.scrub import ChecksumScrubber
            limiter = (MergeRateLimiter(self.scrub_io_mbps)
                       if self.scrub_io_mbps else None)
            # media-contention gate: when the target stack carries a
            # DeviceThrottle (walk the wrapper chain — Retrying /
            # FaultInjecting / Throttled all expose ``inner``), periodic
            # sweeps defer while ingest keeps the device saturated
            gate, d = None, self.target_dir
            while d is not None:
                thr = getattr(d, "throttle", None)
                if thr is not None:
                    from repro.storage.scrub import throttle_saturation_gate
                    gate = throttle_saturation_gate(thr)
                    break
                d = getattr(d, "inner", None)
            scrub_dir = (self._postings_cache.inner
                         if self._postings_cache is not None
                         else self.target_dir)
            self.scrubber = ChecksumScrubber(
                scrub_dir, store=self.store, limiter=limiter,
                interval_s=self.scrub_every or 0.0, contention=gate)
            self.scrubber.start()   # no-op unless scrub_every > 0
        if self.refresh_every is None:
            self.refresh_every = getattr(self.cfg, "refresh_every", 0.0)
        self._stop_refresh = threading.Event()
        self._refresh_error = None
        self._refresh_thread = None
        if self.refresh_every and self.refresh_every > 0:
            self._refresh_thread = threading.Thread(
                target=self._refresh_loop, name="nrt-refresh", daemon=True)
            self._refresh_thread.start()

    def _replay_wal(self):
        """Re-apply every readable WAL record through the normal ingest
        paths, in sequence order. Doc-id allocation is deterministic —
        ``_next_doc`` resumed from the committed max and replay order
        equals original ack order — so every acked doc reappears under
        its original id. Torn/rotted records (never acked) are skipped
        and counted by the log."""
        self._wal_replaying = True
        try:
            for _seq, op, payload in self._wal.replay():
                if op == "add":
                    self.index_batch(payload)
                else:
                    self.delete(payload)
        finally:
            self._wal_replaying = False

    def index_batch(self, tokens: np.ndarray):
        """tokens: (D, L) int32 host buffer. Accumulates in the in-memory
        buffer (the paper's RAM-budget inversion); flushes a segment when
        the flush policy's budget fills.

        With the WAL enabled the batch is logged + synced *before* any
        state changes: a return from this method means the docs survive
        kill -9 even though they are only in the in-memory buffer. A
        failed log append (e.g. ENOSPC past retries) therefore leaves the
        indexer exactly as before the call — the batch was never acked.

        With ``wal_group`` the record is appended under the lock (replay
        order = allocation order stays deterministic) but the durability
        barrier runs after releasing it, coalescing with concurrent
        ackers into one batched fsync; the return still waits for the
        record to be durable. A sync failure then surfaces here with the
        buffer already holding the batch — at-least-once instead of the
        default's exactly-as-if-never-called, the classic group-commit
        trade."""
        seq, out = None, None
        with self._flush_lock:
            if self._wal is not None and not self._wal_replaying:
                from repro.storage.wal import encode_wal_add
                seq = self._wal.append(encode_wal_add(tokens),
                                       sync=not self.wal_group)
            self.stats.docs += tokens.shape[0]
            self.stats.tokens += int((tokens > 0).sum())
            self.stats.read_bytes += tokens.nbytes
            if self._flush_policy.add(tokens):
                out = self._flush()
        if seq is not None and self.wal_group:
            self._wal.sync_upto(seq)
        return out

    def delete(self, doc_ids) -> int:
        """Tombstone ``doc_ids`` (absolute ids, any shape). Buffered like
        Lucene's ``BufferedUpdates``: the ids are folded into the live
        segment set at the next flush/refresh/commit, so every snapshot
        taken after this call returns excludes them (ids never indexed
        are silently ignored). Cheap: no segment bytes move until a merge
        compacts the tombstones away. Returns the ids acknowledged."""
        ids = np.unique(np.asarray(doc_ids, np.int64).reshape(-1))
        if ids.size == 0:
            return 0
        seq = None
        with self._flush_lock:
            if self._wal is not None and not self._wal_replaying:
                from repro.storage.wal import encode_wal_delete
                seq = self._wal.append(encode_wal_delete(ids),
                                       sync=not self.wal_group)
            self._buffered_deletes = np.union1d(self._buffered_deletes, ids)
            self.stats.deletes += int(ids.size)
        if seq is not None and self.wal_group:
            self._wal.sync_upto(seq)
        return int(ids.size)

    def update(self, doc_id: int, doc: np.ndarray):
        """Replace one document (Lucene's ``updateDocument``): tombstone
        ``doc_id`` and buffer ``doc``'s tokens as a new document under the
        existing lock — doc-id allocation is unchanged, the replacement
        gets the next fresh id at flush. Both sides surface together at
        the next flush/refresh: no snapshot ever sees old and new at
        once. Returns ``index_batch``'s result (a segment if the buffer
        flushed)."""
        doc = np.asarray(doc, np.int32)
        if doc.ndim == 1:
            doc = doc[None]
        assert doc.shape[0] == 1, "update replaces exactly one document"
        with self._flush_lock:
            self.delete([doc_id])
            self.stats.updates += 1
            return self.index_batch(doc)

    def _apply_deletes_locked(self, drain: bool):
        """Fold buffered deletes into the live segment set (callers hold
        ``_flush_lock``). The buffer is only DRAINED when every doc that
        could be a target has left the in-memory token buffer (right
        after a flush, or whenever nothing is awaiting one) — a delete
        for a doc still awaiting flush must survive to be re-applied once
        that doc's segment exists. Re-application is idempotent
        (``with_deletes`` no-ops), but draining eagerly keeps a
        delete-only serving workload (NRT daemon, no ingest) from
        rescanning an ever-growing buffer every tick."""
        ids = self._buffered_deletes
        if not ids.size:
            return
        self.merger.apply_deletes(ids)
        if drain:
            self._buffered_deletes = np.zeros(0, np.int64)
        elif self._flush_policy.pending_docs == 0:
            # nothing awaits flush: every id below the allocation frontier
            # has landed wherever it ever will; only ids of docs not yet
            # allocated (meaningless until a future flush) stay buffered
            self._buffered_deletes = ids[ids >= self._next_doc]

    def _flush(self):
        with self._flush_lock:
            return self._flush_locked()

    def _flush_locked(self):
        if self._flush_policy.pending_docs == 0:
            self._apply_deletes_locked(drain=True)
            if self._wal is not None:
                # nothing buffered: every logged op's effect is in the
                # live segment set, so the whole log is commit-covered
                self._wal_covered = self._wal.next_seq - 1
            return None
        t0 = time.time()
        tokens = self._flush_policy.take()
        D = tokens.shape[0]
        base = self._next_doc
        self._next_doc += D
        run = self._jit_invert(jnp.asarray(tokens), base)
        run_np = {k: np.asarray(getattr(run, k)) for k in run._fields}
        seg = segment_from_run(run_np, np.arange(base, base + D),
                               run_np["doc_len"])
        if getattr(self.cfg, "reorder_on_flush", False):
            # BP doc-id reassignment at flush time: the freshest (and most
            # queried, under NRT churn) segments get impact-homogeneous
            # blocks too, not just merge outputs. Scores stay bit-identical
            # (the permutation only relabels local slots).
            perm = reassign_doc_ids(seg)
            if perm is not None:
                seg = replace(seg, reorder=perm)
        self.merger.add_flush(seg)
        # Lucene's BufferedUpdates contract: deletes land WITH the flush
        # (after it, so deletes targeting docs in this very buffer hit
        # the segment they just became), then the buffer drains
        self._apply_deletes_locked(drain=True)
        if self._wal is not None:
            # every record appended before this flush (same lock) is now
            # represented in flushed segments + applied deletes: the next
            # successful commit makes them durable and may truncate
            self._wal_covered = self._wal.next_seq - 1
        self.stats.flushed_bytes += seg.total_bytes()
        self.stats.wall_s += time.time() - t0
        return seg

    def index_spooled(self, directory=None) -> int:
        """Stream the spooled source collection (``data.corpus`` batches
        written through a source ``Directory``) into the index; source
        reads are measured on that directory. Returns docs indexed."""
        from repro.data.corpus import iter_spooled
        directory = directory if directory is not None else self.source_dir
        assert directory is not None, "index_spooled needs a source_dir"
        n = 0
        for _, tokens in iter_spooled(directory):
            self.index_batch(tokens)
            n += tokens.shape[0]
        return n

    def commit(self, flush: bool = True) -> int:
        """Durable commit point: flush buffered docs and deletes, then
        publish the live segment set as ``segments_N`` (two-phase rename
        — per-segment ``.liv`` delete generations are written first and
        referenced by the manifest) and delete superseded files. Returns
        the new commit generation."""
        assert self.store is not None, "commit() requires target_dir"
        with self._flush_lock:
            if flush:
                self._flush_locked()
            else:
                self._apply_deletes_locked(drain=False)
            covered = self._wal_covered
        gen = self.store.commit(self.merger.live_segments())
        if self._wal is not None and covered >= 0:
            # only once the commit is durable are its records disposable
            self._wal.truncate_upto(covered)
        if self.publisher is not None:
            self.publisher.on_commit(gen)   # shippable to replicas now
        return gen

    def finalize(self) -> Segment:
        """Force-merge to the paper's single-segment end state (committed
        durably when a target ``Directory`` is attached). With a scheduler
        attached this first drains in-flight cascades (inside
        ``MergeDriver.finalize``); the scheduler stays usable afterwards."""
        self._flush()
        with self._flush_lock:
            covered = self._wal_covered
        final = self.merger.finalize()
        if self.store is not None:
            gen = self.store.commit(self.merger.live_segments())
            if self._wal is not None and covered >= 0:
                self._wal.truncate_upto(covered)
            if self.publisher is not None:
                self.publisher.on_commit(gen)
        return final

    def close(self):
        """Stop the NRT refresh daemon (join), then release the background
        merge pool (no-op when synchronous). A refresh-thread error is
        re-raised here rather than dying silently on a daemon thread."""
        if self._refresh_thread is not None:
            self._stop_refresh.set()
            self._refresh_thread.join(timeout=30)
            assert not self._refresh_thread.is_alive(), \
                "refresh daemon failed to stop"
            self._refresh_thread = None
            if self._refresh_error is not None:
                err, self._refresh_error = self._refresh_error, None
                raise err
        if self.scrubber is not None:
            scrubber, self.scrubber = self.scrubber, None
            scrubber.close()   # re-raises a scrub-thread error
        if self.merge_scheduler is not None:
            self.merge_scheduler.close()
            self.merge_scheduler = None

    def _refresh_loop(self):
        """Daemon body: periodically swap ``self.searcher`` to a fresh
        snapshot (flush=False — the ingest thread owns flushing; buffered
        deletes are still folded in, see ``refresh``)."""
        while not self._stop_refresh.wait(self.refresh_every):
            try:
                self.refresh(flush=False)
            except Exception as e:  # surfaced by close()
                self._refresh_error = e
                return

    def refresh(self, flush: bool = True) -> IndexSearcher:
        """Near-real-time snapshot: everything indexed so far becomes
        searchable without force-merging (Lucene's NRT refresh shape).

        Flushes the in-memory buffer (so buffered docs surface too; pass
        ``flush=False`` to snapshot only already-flushed segments), then
        builds an ``IndexSearcher`` over ``MergeDriver.live_segments()``.
        Readers are reused from ``reader_cache`` for every segment that
        survived since the last refresh; the returned searcher stays valid
        across future flushes/merges — callers swap searchers at their own
        cadence while indexing continues (write-read decoupling).

        Buffered deletes are folded in FIRST either way (``flush=False``
        keeps them buffered for re-application, in case a target doc is
        still in the token buffer), so a snapshot taken after a delete
        was acknowledged never returns the doc."""
        with self._flush_lock:
            if flush:
                self._flush_locked()
            else:
                self._apply_deletes_locked(drain=False)
        t0 = time.time()
        recovery = None
        if self.store is not None and self.store.quarantined:
            from repro.storage.commit import RecoveryInfo
            recovery = RecoveryInfo(
                quarantined=dict(self.store.quarantined))
        searcher = self.reader_cache.refresh(self.merger.live_segments(),
                                             recovery=recovery)
        self.stats.refreshes += 1
        self.stats.last_refresh_s = time.time() - t0
        self.searcher = searcher   # the (atomic) NRT swap
        # serving hooks: swap attached schedulers to the new snapshot —
        # its generation keys result caches, so a content change here IS
        # the exact invalidation event
        for cb in (self.on_refresh or ()):
            cb(searcher)
        return searcher

    def attach_serving(self, scheduler) -> None:
        """Wire a ``QueryScheduler`` into this writer's lifecycle: every
        ``refresh`` swaps the fresh searcher in (the generation key makes
        that an exact result-cache invalidation), and
        ``envelope_report`` grows the ``serve_*`` counters."""
        self.serving = scheduler
        self.on_refresh.append(scheduler.swap_searcher)
        if self.searcher is not None \
                and scheduler.searcher is not self.searcher:
            scheduler.swap_searcher(self.searcher)

    def envelope_report(self) -> dict:
        """Charge measured bytes to the configured media pair."""
        src, tgt = self.media[self.source], self.media[self.target]
        G = self.stats.read_bytes
        merge = self.merger.snapshot()  # atomic vs in-flight merge installs
        W = merge["bytes_written"]
        alpha = merge["amplification"]
        t_read = G / (src.read_bw * env.GB)
        t_write = W / (tgt.write_bw * env.GB)
        t_cpu = (G / env.GB) * self.params.c_idx / self.params.n_cores
        shared = self.source == self.target
        if shared:
            t_io = (G + W) / (tgt.write_bw * env.GB) * self.params.interference
            total = max(t_io, t_cpu)
            bound = "shared-io" if t_io >= t_cpu else "cpu"
        else:
            total = max(t_read, t_cpu, t_write)
            bound = ["read", "cpu", "write"][int(np.argmax(
                [t_read, t_cpu, t_write]))]
        # merge cost: what the model charges the cascade (re-reads from the
        # target + merge re-writes at target bandwidth) next to the wall
        # clock the merges actually took — the modeled-vs-actual gap.
        merge_writes = W - merge["flushed_bytes"]
        t_merge_modeled = (merge["bytes_read_merge"]
                           / (tgt.read_bw * env.GB)
                           + merge_writes / (tgt.write_bw * env.GB))
        report = {
            "alpha_measured": alpha,
            "bytes_read": G, "bytes_written": W,
            "t_read_s": t_read, "t_cpu_s": t_cpu, "t_write_s": t_write,
            "modeled_total_s": total, "bound": bound,
            "gb_per_min_modeled": (G / env.GB) / max(total / 60, 1e-9),
            "docs_per_s_modeled": self.stats.docs / max(total, 1e-9),
            "n_merges": merge["n_merges"],
            "wall_s_host": self.stats.wall_s,
            "t_merge_modeled_s": t_merge_modeled,
            "merge_wall_s": merge["merge_wall_s"],
            "merge_io_paused_s": merge["merge_io_paused_s"],
            # document lifecycle: live vs tombstoned docs in the live set
            "live_docs": merge["live_docs"],
            "deleted_docs": merge["deleted_docs"],
            "deletes_acked": self.stats.deletes,
            "updates_acked": self.stats.updates,
            "merge_concurrency": (self.merge_scheduler.max_threads
                                  if self.merge_scheduler else 0),
            # index size, from the ONE authoritative figure
            # (MergeDriver.snapshot's live_bytes_raw): the model's packed
            # bytes of the live set; the codec's encoded bytes sit beside
            # it once durable storage is attached.
            "index_bytes_raw": merge["live_bytes_raw"],
            "index_bytes_encoded": 0,
        }
        # serving-side pruning counters (core/query.py PruneStats): what
        # the latest refreshed searcher actually decoded + scored vs the
        # candidate blocks an exhaustive pass would have touched
        ps = getattr(self.searcher, "prune_stats", None)
        if ps is None:
            from repro.core.query import PruneStats
            ps = PruneStats()
        from repro.core.searcher import evaluator_cache_hits
        report.update({
            "blocks_candidate": ps.blocks_candidate,
            "blocks_survived": ps.blocks_survived,
            "blocks_scored": ps.blocks_scored,
            "segments_skipped": ps.segments_skipped,
            "prune_skip_rate": ps.skip_rate,
            "terms_eliminated": ps.terms_eliminated,
            "blocks_skipped_midgrid": ps.blocks_skipped_midgrid,
            "evaluator_cache_hits": evaluator_cache_hits(),
        })
        # fault-tolerance surface: is this index serving with holes, and
        # what has the hardened IO path absorbed so far
        if self.store is not None:
            q = dict(self.store.quarantined)
            report.update({
                "degraded": bool(q),
                "missing_docs": sum(int(v or 0) for v in q.values()),
                "segments_quarantined": len(q),
                "segments_healed": self.store.heals,
            })
        else:
            report.update({
                "degraded": bool(getattr(self.searcher, "degraded", False)),
                "missing_docs": int(getattr(self.searcher,
                                            "missing_docs", 0) or 0),
                "segments_quarantined": len(getattr(self.searcher,
                                                    "quarantined", ())
                                            or ()),
            })
        if self._wal is not None:
            report.update({"wal_appends": self._wal.appended,
                           "wal_replayed": self._wal.replayed,
                           "wal_skipped": self._wal.skipped,
                           "wal_group_commits": self._wal.group_commits,
                           "wal_group_acks": self._wal.group_acks,
                           "wal_group_max": self._wal.group_max,
                           "wal_rotations": self._wal.rotations,
                           "wal_recycled": self._wal.recycled,
                           "wal_recycle_reused": self._wal.recycle_reused,
                           "wal_recycle_reclaimed":
                               self._wal.recycle_reclaimed})
        if self.scrubber is not None:
            report.update({f"scrub_{k}": v
                           for k, v in self.scrubber.report().items()
                           if k != "corrupt"})
        d = self.target_dir   # retry wrapper may sit under the cache layer
        while d is not None:
            if hasattr(d, "retries"):
                report["io_retries"] = d.retries
                report["io_giveups"] = d.giveups
                break
            d = getattr(d, "inner", None)
        if self.merge_scheduler is not None:
            report["merge_retries"] = self.merge_scheduler.merge_retries
        if self._postings_cache is not None:
            pc = self._postings_cache
            report.update({
                "postings_cache_hits": pc.cache_hits,
                "postings_cache_misses": pc.cache_misses,
                "postings_cache_evictions": pc.cache_evictions,
                "postings_cache_rejected": pc.cache_rejected,
                "postings_cache_bytes": pc.cache_bytes,
            })
        if self.serving is not None:
            s = self.serving
            report.update({
                "serve_served": s.served,
                "serve_cached": s.served_cached,
                "serve_rejected": s.rejected,
                "serve_steps": s.steps,
                "serve_partial_steps": s.partial_steps,
                "serve_queue_depth": s.queue_depth,
                "serve_degraded": s.degraded,
            })
            if s.cache is not None:
                report["result_cache"] = s.cache.report()
        if self.publisher is not None:
            report["fleet"] = self.publisher.report()
        if self.store is not None:
            report.update(self._measured_report())
        return report

    def _measured_report(self) -> dict:
        """Measured counterpart of the analytic envelope: real bytes that
        crossed the source/target Directories and the device time their
        throttles accumulated (wall time when unthrottled)."""
        live = self.merger.live_segments()
        src_dir, tgt_dir = self.source_dir, self.target_dir
        src_thr = getattr(src_dir, "throttle", None)
        tgt_thr = getattr(tgt_dir, "throttle", None)
        G_m = src_dir.bytes_read if src_dir is not None \
            else self.stats.read_bytes
        # source stage = reads of the spooled collection; target stage =
        # everything charged to the target device (writes + merge re-reads)
        t_src = (src_thr.busy_read_s if src_thr is not None
                 else src_dir.read_wall_s if src_dir is not None else 0.0)
        t_tgt = (tgt_thr.busy_s if tgt_thr is not None
                 else tgt_dir.write_wall_s + tgt_dir.read_wall_s)
        if src_thr is not None and src_thr is tgt_thr:
            # one device serves both streams: its timeline already sums
            # them — the paper's shared-controller serialization, measured
            t_io = src_thr.busy_s
            shared = True
        else:
            t_io = max(t_src, t_tgt)
            shared = False
        t_env = max(t_io, self.stats.wall_s)
        return {
            "bytes_read_measured": G_m,
            "bytes_written_measured": tgt_dir.bytes_written,
            "bytes_read_merge_measured": self.store.bytes_encoded_read,
            "index_bytes_encoded": self.store.encoded_bytes_live(live),
            "codec": self.store.codec,
            "index_bytes_by_file": self.store.encoded_bytes_by_suffix(live),
            "t_source_busy_s": t_src,
            "t_target_busy_s": t_tgt,
            "t_io_measured_s": t_io,
            "shared_media_measured": shared,
            "t_envelope_measured_s": t_env,
            "gb_per_min_measured": (G_m / env.GB) / max(t_io / 60, 1e-12),
        }
