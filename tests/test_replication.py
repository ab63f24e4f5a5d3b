"""Replicated, sharded serving fleet: manifest-shipping replication,
scatter-gather top-k, quarantine-driven failover.

The load-bearing property throughout: a ``FleetSearcher`` over shard
replicas is *bit-identical on scores* to one ``IndexSearcher`` over the
union corpus — under deletes, mid-sync replicas, failover, and the
cross-shard shared pruning bound. Doc lengths and dfs are integers, so
the fleet's union CollectionStats (float64 sums) equal the oracle's
digit for digit regardless of how docs are grouped into shards.

Satellites covered here: WAL group commit (coalescing + kill-9 loses no
acked doc, via ``VolatileDirectory``), contention-aware scrub deferral,
and the multi-process writer/searcher split (``RemoteReplica``).
"""
import threading
import time

import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.registry import get_arch
from repro.core.indexer import DistributedIndexer
from repro.core.searcher import ReaderCache
from repro.data.corpus import TINY, SyntheticCorpus
from repro.replication import (CommitPublisher, FleetSearcher,
                               ReplicaSyncer, ShardSpec, latest_commit_meta,
                               manifest_files, merge_topk_sharded,
                               plan_delta)
from repro.storage import (ChecksumScrubber, RAMDirectory,
                           VolatileDirectory, WriteAheadLog, open_latest,
                           throttle_saturation_gate)

from test_distributed import run_with_devices

CFG = get_arch("lucene-envelope").smoke
CORPUS = SyntheticCorpus(TINY, doc_buffer_len=CFG.doc_len)
RANGE = 1_000_000   # range-shard width: shard i owns [i*RANGE, (i+1)*RANGE)


def _build_shard(si, n_batches=2, per=16, delete=False):
    """One shard writer over its own directory, publisher attached."""
    d = RAMDirectory()
    pub = CommitPublisher(d)
    ix = DistributedIndexer(cfg=CFG, target_dir=d, publisher=pub,
                            doc_base=si * RANGE)
    for i in range(n_batches):
        ix.index_batch(CORPUS.batch(8 * si + i, per))
    if delete:
        ix.delete(np.arange(si * RANGE + 1, si * RANGE + 5))
    ix.commit()
    return ix, pub


def _replicas(ix, pub, n=1, tag="s0"):
    """n synced replicas of one shard, peers cross-wired."""
    group = [ReplicaSyncer(RAMDirectory(), ix.target_dir,
                           replica_id=f"{tag}r{ri}", publisher=pub)
             for ri in range(n)]
    for r in group:
        assert r.sync_once() is not None
        r.peers = [p.directory for p in group if p is not r]
    return group


def _union_oracle(dirs, prune=False):
    """Single searcher over the union of the shards' committed segments
    — the exhaustive ground truth the fleet must match score for score."""
    segs = []
    for d in dirs:
        _, s = open_latest(d)
        segs.extend(s)
    return ReaderCache(prune=prune).refresh(segs)


def _queries(batches, B, Q=3, seed=0):
    v = np.unique(np.concatenate([CORPUS.batch(b, 16).ravel()
                                  for b in batches]))
    v = v[v > 0]
    rng = np.random.default_rng(seed)
    return rng.choice(v, size=(B, Q), replace=True).astype(np.int32)


# ---------------------------------------------------------------------------
# manifest shipping
# ---------------------------------------------------------------------------

def test_plan_delta_ships_only_missing_owned_files():
    ix, _ = _build_shard(0)
    gen, meta, manifest = latest_commit_meta(ix.target_dir)
    assert gen >= 1 and manifest
    files = manifest_files(meta)
    assert files and all(not f.startswith("segments_") for f in files)
    # cold replica: fetch everything the manifest references
    plan = plan_delta(gen, meta, set())
    assert set(plan.to_fetch) == set(files) and not plan.up_to_date
    # current replica: nothing to ship, nothing to drop
    assert plan_delta(gen, meta, set(files)).up_to_date
    # warm replica holding a foreign file and a stale owned file: the
    # delta never ships what it has, never deletes what it doesn't own
    have = set(files) | {"notes.txt", "sdeadbeef.doc", "segments_0"}
    plan = plan_delta(gen, meta, have)
    assert not plan.to_fetch
    assert "notes.txt" not in plan.to_delete
    assert "sdeadbeef.doc" in plan.to_delete
    assert "segments_0" in plan.to_delete          # older manifest GCs
    assert plan.manifest not in plan.to_delete


def test_publisher_ledger_tracks_lag_and_backlog():
    ix, pub = _build_shard(0)
    group = _replicas(ix, pub, n=2)
    rep = pub.report()
    assert rep["replicas"] == 2 and rep["replicas_current"] == 2
    assert rep["bytes_shipped_total"] > 0
    assert rep["max_replication_lag_s"] >= 0.0
    for r in rep["per_replica"].values():
        assert r["gen"] == rep["last_gen"] and not r["behind"]
    # writer advances; the ledger flips the replicas to behind until
    # they pull the new commit (and the second pull ships only deltas)
    ix.index_batch(CORPUS.batch(6, 16))
    ix.commit()
    assert all(r["behind"] for r in pub.report()["per_replica"].values())
    first_bytes = group[0].bytes_fetched
    out = group[0].sync_once()
    assert out is not None and out["gen"] == pub.report()["last_gen"]
    assert out["lag_s"] >= 0.0
    delta_bytes = group[0].bytes_fetched - first_bytes
    assert 0 < delta_bytes < first_bytes    # delta, not a full re-ship
    assert group[0].sync_once() is None     # idempotent once current
    assert pub.report()["per_replica"]["s0r0"]["behind"] == 0


# ---------------------------------------------------------------------------
# scatter-gather exactness (the tentpole property)
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(st.integers(1, 3), st.booleans(), st.sampled_from([3, 10]),
       st.integers(1, 3))
def test_fleet_topk_matches_union_oracle(n_shards, delete, k, B):
    """Hypothesis oracle: fleet scatter-gather (cross-shard theta
    sharing, union stats) == exhaustive single-index search over the
    union corpus, exactly — with and without tombstoned deletes."""
    writers = [_build_shard(si, delete=delete) for si in range(n_shards)]
    shards = [_replicas(ix, pub, n=1, tag=f"s{si}")
              for si, (ix, pub) in enumerate(writers)]
    fleet = FleetSearcher(shards)
    oracle = _union_oracle([ix.target_dir for ix, _ in writers])
    q = _queries([8 * si + i for si in range(n_shards) for i in range(2)],
                 B, seed=n_shards * 31 + k)
    fv, fi = fleet.search_batched(q, k)
    ov, oi = oracle.search_batched(q, k)
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(ov))
    rep = fleet.report()
    assert rep["shards_visited"] + rep["shards_skipped"] == n_shards


def test_fleet_exact_under_mid_sync_replica_then_converges():
    """A replica one commit behind still serves an exact fleet — over
    the union of what the chosen replicas HOLD; after it catches up the
    fleet equals the oracle over the writers' latest commits."""
    ix0, pub0 = _build_shard(0, n_batches=1)
    (r0,) = _replicas(ix0, pub0)
    ix0.index_batch(CORPUS.batch(1, 16))     # r0 is now one commit behind
    ix0.commit()
    ix1, pub1 = _build_shard(1)
    (r1,) = _replicas(ix1, pub1)
    fleet = FleetSearcher([[r0], [r1]])
    q = _queries([0, 1, 8, 9], B=3, seed=5)
    # mid-sync: the fleet view is the union of the replica snapshots
    ov, _ = _union_oracle([r0.directory, r1.directory]).search_batched(q, 10)
    fv, _ = fleet.search_batched(q, 10)
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(ov))
    # converged: the fleet view is the union of the writers' commits
    assert r0.sync_once()["gen"] == 2
    ov, _ = _union_oracle([ix0.target_dir,
                           ix1.target_dir]).search_batched(q, 10)
    fv, _ = fleet.search_batched(q, 10)
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(ov))


def test_fleet_matches_force_merged_union_after_finalize():
    """After the writers force-merge (deletes compacted into one segment
    per shard) and the replicas re-sync, the fleet is score-identical to
    exhaustive search over the force-merged union index."""
    writers = [_build_shard(si, delete=True) for si in range(2)]
    shards = [_replicas(ix, pub, tag=f"s{si}")
              for si, (ix, pub) in enumerate(writers)]
    for ix, _ in writers:
        final = ix.finalize()
        assert not final.has_deletes
    for group in shards:
        assert group[0].sync_once() is not None
    fleet = FleetSearcher(shards)
    oracle = _union_oracle([ix.target_dir for ix, _ in writers])
    assert len(oracle.readers) == 2          # one segment per shard
    q = _queries([0, 1, 8, 9], B=4, seed=11)
    fv, _ = fleet.search_batched(q, 10)
    ov, _ = oracle.search_batched(q, 10)
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(ov))


def test_shard_spec_assignment():
    rs = ShardSpec(n_shards=3, policy="range", range_size=RANGE)
    ids = np.array([0, RANGE - 1, RANGE, 2 * RANGE, 5 * RANGE])
    np.testing.assert_array_equal(rs.shard_of(ids), [0, 0, 1, 2, 2])
    hs = ShardSpec(n_shards=4, policy="hash")
    s = hs.shard_of(np.arange(1000))
    assert s.min() >= 0 and s.max() < 4
    assert all((s == i).sum() > 0 for i in range(4))    # no empty shard
    np.testing.assert_array_equal(s, hs.shard_of(np.arange(1000)))


def test_merge_topk_sharded_host_path():
    rng = np.random.default_rng(3)
    S, B, k = 4, 3, 8
    vals = rng.permutation(S * B * k).reshape(S, B, k).astype(np.float32)
    ids = np.arange(S * B * k, dtype=np.int32).reshape(S, B, k)
    mv, mi = merge_topk_sharded(vals, ids, k)
    mv, mi = np.asarray(mv), np.asarray(mi)
    for b in range(B):
        flat_v = vals[:, b, :].ravel()
        top = np.sort(flat_v)[::-1][:k]
        np.testing.assert_array_equal(mv[b], top)
        # each returned id carries its own value
        pos = {int(i): float(v) for v, i in zip(flat_v,
                                                ids[:, b, :].ravel())}
        assert all(pos[int(i)] == float(v) for v, i in zip(mv[b], mi[b]))
    # k larger than the available pool pads with (0, -1)
    pv, pi = merge_topk_sharded(vals[:1, :, :2], ids[:1, :, :2], k)
    assert np.asarray(pv).shape == (B, k)
    assert (np.asarray(pi)[:, 2:] == -1).all()


def test_merge_topk_sharded_mesh_matches_host():
    out = run_with_devices("""
        import jax, numpy as np
        from repro.replication.fleet import merge_topk_sharded
        rng = np.random.default_rng(0)
        S, B, k = 4, 3, 8
        vals = rng.permutation(S*B*k).reshape(S, B, k).astype(np.float32)
        ids = np.arange(S*B*k, dtype=np.int32).reshape(S, B, k)
        hv, hi = merge_topk_sharded(vals, ids, k)
        mesh = jax.make_mesh((4,), ("shard",))
        mv, mi = merge_topk_sharded(vals, ids, k, mesh=mesh)
        assert np.array_equal(np.asarray(hv), np.asarray(mv))
        assert np.array_equal(np.asarray(hi), np.asarray(mi))
        print("MESH-TOPK-OK")
    """, n=4)
    assert "MESH-TOPK-OK" in out


def test_replica_readers_stay_on_their_device():
    """A replica given ``device=`` keeps every reader array on that
    device through a later sync that runs on another thread (as the
    background poll does), where JAX's thread-local default device is
    the process default — both the new segment's build and the delete
    generation's reopen."""
    out = run_with_devices("""
        import threading
        import jax, numpy as np
        from repro.configs.registry import get_arch
        from repro.core.indexer import DistributedIndexer
        from repro.core.searcher import _index_arrays
        from repro.data.corpus import TINY, SyntheticCorpus
        from repro.replication import ReplicaSyncer
        from repro.storage import RAMDirectory
        cfg = get_arch("lucene-envelope").smoke
        corpus = SyntheticCorpus(TINY, doc_buffer_len=cfg.doc_len)
        devs = jax.devices()

        def placement(syncer):
            arrs = []
            for r in syncer.searcher.readers:
                arrs += list(_index_arrays(r.index)) + [r.doc_map]
                arrs += [] if r.live is None else [r.live]
            return (set().union(*(a.devices() for a in arrs)),
                    all(a.committed for a in arrs))

        for si in (1, 3):
            base = si * 1_000_000
            ix = DistributedIndexer(cfg=cfg, target_dir=RAMDirectory(),
                                    doc_base=base)
            ix.index_batch(corpus.batch(2 * si, 16))
            ix.commit()
            syncer = ReplicaSyncer(RAMDirectory(), ix.target_dir,
                                   device=devs[si])
            assert syncer.sync_once() is not None
            assert placement(syncer) == ({devs[si]}, True), placement(syncer)
            ix.index_batch(corpus.batch(2 * si + 1, 16))
            ix.delete(np.arange(base, base + 4))
            ix.commit()
            got = []
            t = threading.Thread(target=lambda: got.append(syncer.sync_once()))
            t.start()
            t.join()
            assert got and got[0] is not None
            readers = syncer.searcher.readers
            assert len(readers) >= 2 and any(r.live is not None
                                             for r in readers)
            assert placement(syncer) == ({devs[si]}, True), placement(syncer)
        print("REPLICA-DEVICE-OK")
    """, n=4)
    assert "REPLICA-DEVICE-OK" in out


# ---------------------------------------------------------------------------
# quarantine-driven failover
# ---------------------------------------------------------------------------

def test_quarantine_sheds_traffic_with_zero_failed_queries():
    ix0, pub0 = _build_shard(0)
    ix1, pub1 = _build_shard(1)
    g0 = _replicas(ix0, pub0, n=2)
    fleet = FleetSearcher([g0, _replicas(ix1, pub1, tag="s1")])
    oracle = _union_oracle([ix0.target_dir, ix1.target_dir])
    bad = g0[0]
    seg_file = next(n for n in bad.directory.list_files()
                    if n.endswith(".pst"))
    bad.quarantine(seg_file)
    assert not bad.healthy and bad.missing_docs > 0
    assert not fleet.degraded      # the healthy peer covers the shard
    failed = 0
    for trial in range(8):
        q = _queries([0, 1, 8, 9], B=2, seed=100 + trial)
        fv, _ = fleet.search_batched(q, 10)
        ov, _ = oracle.search_batched(q, 10)
        if not np.array_equal(np.asarray(fv), np.asarray(ov)):
            failed += 1
    rep = fleet.report()
    assert failed == 0
    assert rep["failovers"] >= 1 and rep["degraded_served"] == 0
    assert rep["served"].get("s0r0", 0) == 0   # shed everything to s0r1


def test_repair_refetches_corrupt_segment_from_peer():
    ix, pub = _build_shard(0)
    g = _replicas(ix, pub, n=2)
    bad, peer = g
    seg_file = next(n for n in bad.directory.list_files()
                    if n.endswith(".doc"))
    data = bytearray(bad.directory.read_file(seg_file))
    data[len(data) // 2] ^= 0xFF               # bit rot on bad's media
    bad.directory.write_file(seg_file, bytes(data))
    base = bad.quarantine(seg_file)
    assert not bad.healthy
    out = bad.repair(base)
    assert out["files"] >= 1 and out["bytes"] > 0
    assert bad.healthy and bad.missing_docs == 0
    assert bad.refetches >= 1
    # the healed copy serves the same scores as the untouched peer
    q = _queries([0, 1], B=2, seed=7)
    fleet = FleetSearcher([g])
    fv, _ = fleet.search_batched(q, 10)
    ov, _ = _union_oracle([ix.target_dir]).search_batched(q, 10)
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(ov))


def test_anti_entropy_detects_and_heals_bit_rot():
    ix, pub = _build_shard(0)
    g = _replicas(ix, pub, n=2)
    bad = g[0]
    victim = next(n for n in bad.directory.list_files()
                  if n.endswith(".dict"))
    data = bytearray(bad.directory.read_file(victim))
    data[-3] ^= 0x40
    bad.directory.write_file(victim, bytes(data))
    # a vanished referenced file is detected too
    gone = next(n for n in bad.directory.list_files()
                if n.endswith(".pos"))
    bad.directory.delete_file(gone)
    out = bad.anti_entropy()
    assert victim in out["corrupt"] and gone in out["corrupt"]
    assert out["repaired"] and bad.healthy
    assert bad.directory.file_exists(gone)
    rep = bad.report()
    assert rep["repairs"] >= 1 and rep["refetch_bytes"] > 0


# ---------------------------------------------------------------------------
# latency-aware replica routing (ISSUE 9 satellite)
# ---------------------------------------------------------------------------

class _SlowReplica:
    """Duck-typed replica wrapper: same snapshot, slower serves."""

    def __init__(self, inner, delay_s):
        self._inner = inner
        self._delay_s = delay_s

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def search_batched(self, q, k, theta0=None):
        time.sleep(self._delay_s)
        return self._inner.search_batched(q, k, theta0=theta0)


def test_slow_replica_sheds_traffic_with_zero_failed_queries():
    """EWMA routing: a replica that is merely SLOW (healthy, identical
    content) sheds most traffic to its faster peer after the round-robin
    warmup, while every answer stays bit-identical to the union oracle —
    and periodic probe picks keep refreshing its latency estimate."""
    ix, pub = _build_shard(0)
    g = _replicas(ix, pub, n=2)
    slow = _SlowReplica(g[0], 0.02)
    fleet = FleetSearcher([[slow, g[1]]], probe_every=8)
    oracle = _union_oracle([ix.target_dir])
    q = _queries([0, 1], B=2, seed=3)
    trials = 24
    for _ in range(trials):
        fv, _ = fleet.search_batched(q, 10)
        ov, _ = oracle.search_batched(q, 10)
        np.testing.assert_array_equal(np.asarray(fv), np.asarray(ov))
    rep = fleet.report()
    assert rep["lat_routed"] > 0
    served = rep["served"]
    assert served["s0r0"] + served["s0r1"] == trials
    assert served["s0r0"] <= 4          # rr warmup + probes only
    assert served["s0r1"] >= trials - 4
    # the EWMA table ranks the replicas honestly
    assert rep["latency_ms"]["s0r0"] > rep["latency_ms"]["s0r1"]
    # slowness is not unhealth: no failover, no degraded serving
    assert rep["failovers"] == 0 and rep["degraded_served"] == 0


def test_latency_aware_off_restores_round_robin():
    ix, pub = _build_shard(0)
    g = _replicas(ix, pub, n=2)
    slow = _SlowReplica(g[0], 0.005)
    fleet = FleetSearcher([[slow, g[1]]], latency_aware=False)
    q = _queries([0, 1], B=2, seed=4)
    for _ in range(8):
        fleet.search_batched(q, 10)
    rep = fleet.report()
    assert rep["lat_routed"] == 0
    assert rep["served"]["s0r0"] == rep["served"]["s0r1"] == 4


# ---------------------------------------------------------------------------
# WAL group commit (satellite)
# ---------------------------------------------------------------------------

def test_wal_group_commit_coalesces_acks_per_fsync():
    """A blocked sync leader makes concurrent appenders share ONE
    barrier: 3 acked records, 2 fsync groups (leader + coalesced pair)."""
    class SlowSync(VolatileDirectory):
        def __init__(self, gate):
            super().__init__()
            self.gate = gate
            self.sync_calls = []

        def _sync(self, names):
            self.gate.wait(10)
            self.sync_calls.append(sorted(names))
            super()._sync(names)

    gate = threading.Event()
    d = SlowSync(gate)
    wal = WriteAheadLog(d)
    errs = []

    def appender():
        try:
            wal.sync_upto(wal.append(b"A" + bytes(16), sync=False))
        except Exception as e:          # pragma: no cover - diagnostic
            errs.append(e)

    t0 = threading.Thread(target=appender)
    t0.start()
    time.sleep(0.2)                    # t0 is the leader, parked in _sync
    rest = [threading.Thread(target=appender) for _ in range(2)]
    for t in rest:
        t.start()
    time.sleep(0.2)                    # both queued behind the leader
    gate.set()
    t0.join()
    for t in rest:
        t.join()
    assert not errs
    assert wal.appended == 3 and wal.group_acks == 3
    assert wal.group_commits == 2 and wal.group_max == 2
    assert len(d.sync_calls) == 2 and len(d.sync_calls[1]) == 2


def test_wal_group_kill9_loses_no_acked_doc():
    """kill -9 mid-group (volatile page cache dropped): every ACKED doc
    replays; a written-but-never-synced record may vanish, silently."""
    vol = VolatileDirectory()
    ix = DistributedIndexer(cfg=CFG, target_dir=vol, wal=True,
                            wal_group=True)
    threads = [threading.Thread(
        target=lambda i=i: ix.index_batch(CORPUS.batch(i, 4)))
        for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert ix._wal.group_acks == 6
    rep = ix.envelope_report()
    assert rep["wal_group_acks"] == 6 and rep["wal_group_commits"] >= 1
    ix._wal.append(b"A" + bytes(16), sync=False)   # never acked
    survivor = vol.crash()
    ix2 = DistributedIndexer(cfg=CFG, target_dir=survivor, wal=True)
    assert ix2.refresh().n_docs == 6 * 4
    ix2.close()


# ---------------------------------------------------------------------------
# contention-aware scrub scheduling (satellite)
# ---------------------------------------------------------------------------

def test_scrub_defers_while_media_saturated():
    d = RAMDirectory()
    ix = DistributedIndexer(cfg=CFG, target_dir=d)
    ix.index_batch(CORPUS.batch(0, 8))
    ix.commit()
    sat = {"on": True}
    sc = ChecksumScrubber(d, contention=lambda: sat["on"])
    assert sc.maybe_sweep() is None            # deferred under pressure
    assert sc.sweeps_deferred == 1 and sc.sweeps == 0
    assert sc.sweep() == []                    # explicit sweep always runs
    sat["on"] = False
    assert sc.maybe_sweep() == []              # resumes on the idle tick
    assert sc.sweeps == 2 and sc.report()["sweeps_deferred"] == 1


def test_throttle_saturation_gate_measures_current_regime():
    class FakeThrottle:
        busy_s = 0.0

    thr = FakeThrottle()
    gate = throttle_saturation_gate(thr, threshold=0.5)
    time.sleep(0.01)
    assert gate() is False                     # idle: no busy time accrued
    thr.busy_s += 100.0                        # a burst of ingest IO
    time.sleep(0.01)
    assert gate() is True
    time.sleep(0.01)
    assert gate() is False                     # burst over, regime reset


# ---------------------------------------------------------------------------
# multi-process fleet (writer + searcher replicas as real processes)
# ---------------------------------------------------------------------------

def test_remote_replica_processes_converge_and_heal(tmp_path):
    from repro.replication import RemoteReplica
    from repro.storage import FSDirectory

    src = FSDirectory(str(tmp_path / "writer"))
    pub = CommitPublisher(src)
    ix = DistributedIndexer(cfg=CFG, target_dir=src, publisher=pub)
    for i in range(2):
        ix.index_batch(CORPUS.batch(i, 16))
    ix.commit()
    paths = [tmp_path / "r0", tmp_path / "r1"]
    reps = [RemoteReplica(f"r{i}", paths[i], tmp_path / "writer",
                          peer_paths=[paths[1 - i]]).start()
            for i in range(2)]
    try:
        for r in reps:
            out = r.sync_once()
            assert out["gen"] == 1 and r.gen == 1 and r.healthy
            assert r.platform == "cpu"     # host processes, said explicitly
        # convergence tracks EVERY commit
        ix.index_batch(CORPUS.batch(2, 16))
        ix.commit()
        for r in reps:
            assert r.sync_once()["gen"] == 2
        fleet = FleetSearcher([reps])
        q = _queries([0, 1, 2], B=2, seed=3)
        fv, _ = fleet.search_batched(q, 10)
        ov, _ = _union_oracle([src]).search_batched(q, 10)
        np.testing.assert_array_equal(np.asarray(fv), np.asarray(ov))
        # bit rot on r0's disk: scrub detects, the PEER process heals it
        d0 = FSDirectory(str(paths[0]))
        victim = next(n for n in d0.list_files() if n.endswith(".doc"))
        data = bytearray(d0.read_file(victim))
        data[len(data) // 2] ^= 0xFF
        d0.write_file(victim, bytes(data))
        out = reps[0].anti_entropy()
        assert victim in out["corrupt"] and reps[0].healthy
        fv2, _ = fleet.search_batched(q, 10)
        np.testing.assert_array_equal(np.asarray(fv2), np.asarray(ov))
        assert reps[0].report()["repairs"] >= 1
    finally:
        for r in reps:
            r.close()
    ix.close()
