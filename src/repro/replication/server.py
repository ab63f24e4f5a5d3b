"""Multi-process fleet plumbing: a replica in its own OS process.

``replica_main`` runs a ``ReplicaSyncer`` in a child process over real
``FSDirectory`` paths and serves a tiny command loop on a multiprocessing
``Pipe``; ``RemoteReplica`` is the parent-side proxy that speaks the same
duck-typed replica protocol as an in-process syncer (``collection_stats``
/ ``install_stats`` / ``query_max_ub`` / ``search_batched`` / ``epoch``
/ ``healthy``), so a ``FleetSearcher`` serves a mix of local and remote
replicas without knowing which is which.

This is the writer/searcher separation the paper's media-isolation
result points at, made literal: the writer process owns the write medium
and never serves; each searcher process owns its own directory (its own
media profile) and never writes anything but replicated bytes. The only
channel between them is the filesystem the manifests ship over — the
command pipe carries queries and control, never index data.

Replica processes are HOST processes: each child runs JAX on
``REPLICA_PLATFORM`` (the CPU), set explicitly before its first JAX
import so that no child takes the accelerator the parent or the writer
holds. The child reports the platform it really got
(``jax.devices()[0].platform``) in its state, readable as
``RemoteReplica.platform``; a fleet that must serve from the chip runs
its replicas in-process, one device each.

``epoch``/``healthy``/``missing_docs`` are cached parent-side and
re-read after every state-changing call (sync/quarantine/repair), so the
fleet's hot routing path costs no IPC beyond the search itself.
"""
from __future__ import annotations

import multiprocessing as mp
import os

import numpy as np

REPLICA_PLATFORM = "cpu"


def replica_main(conn, replica_id: str, local_path: str, source_path: str,
                 peer_paths=(), prune: bool = True) -> None:
    """Child-process entry: serve one replica on ``REPLICA_PLATFORM``
    until ``stop``."""
    os.environ["JAX_PLATFORMS"] = REPLICA_PLATFORM
    import jax
    from repro.replication.syncer import ReplicaSyncer
    from repro.storage.directory import FSDirectory
    syncer = ReplicaSyncer(
        FSDirectory(local_path), FSDirectory(source_path),
        peers=[FSDirectory(p) for p in peer_paths],
        replica_id=replica_id, prune=prune)

    def state():
        return {"epoch": syncer.epoch, "healthy": syncer.healthy,
                "missing_docs": syncer.missing_docs, "gen": syncer.gen,
                "platform": jax.devices()[0].platform}

    while True:
        try:
            cmd, payload = conn.recv()
        except (EOFError, OSError):
            return
        try:
            if cmd == "stop":
                conn.send(("ok", None))
                return
            elif cmd == "sync":
                out = syncer.sync_once()
                conn.send(("ok", (out, state())))
            elif cmd == "stats":
                conn.send(("ok", syncer.collection_stats()))
            elif cmd == "install_stats":
                syncer.install_stats(payload)
                conn.send(("ok", None))
            elif cmd == "ub":
                conn.send(("ok", np.asarray(syncer.query_max_ub(payload))))
            elif cmd == "search":
                q, k, theta0 = payload
                v, i = syncer.search_batched(q, k, theta0=theta0)
                conn.send(("ok", (np.asarray(v), np.asarray(i))))
            elif cmd == "quarantine":
                conn.send(("ok", (syncer.quarantine(payload), state())))
            elif cmd == "repair":
                conn.send(("ok", (syncer.repair(payload), state())))
            elif cmd == "anti_entropy":
                conn.send(("ok", (syncer.anti_entropy(), state())))
            elif cmd == "report":
                conn.send(("ok", syncer.report()))
            elif cmd == "state":
                conn.send(("ok", state()))
            else:
                conn.send(("err", f"unknown command {cmd!r}"))
        except BaseException as e:    # keep serving; parent decides
            try:
                conn.send(("err", f"{type(e).__name__}: {e}"))
            except (BrokenPipeError, OSError):
                return


class RemoteReplicaError(RuntimeError):
    """A remote replica's command raised; the message carries the child's
    exception repr."""


class RemoteReplica:
    """Parent-side proxy over one searcher process (see module doc)."""

    def __init__(self, replica_id: str, local_path: str, source_path: str,
                 peer_paths=(), prune: bool = True, ctx=None):
        self.replica_id = replica_id
        self._args = (replica_id, str(local_path), str(source_path),
                      [str(p) for p in peer_paths], prune)
        self._ctx = ctx if ctx is not None else mp.get_context("spawn")
        self._proc = None
        self._conn = None
        self._state = {"epoch": 0, "healthy": True,
                       "missing_docs": 0, "gen": 0, "platform": None}

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "RemoteReplica":
        parent, child = self._ctx.Pipe()
        self._proc = self._ctx.Process(
            target=replica_main, args=(child,) + self._args,
            name=f"replica-{self.replica_id}", daemon=True)
        self._proc.start()
        child.close()
        self._conn = parent
        return self

    def close(self) -> None:
        if self._conn is not None:
            try:
                self._conn.send(("stop", None))
                self._conn.recv()
            except (BrokenPipeError, EOFError, OSError):
                pass
            self._conn.close()
            self._conn = None
        if self._proc is not None:
            self._proc.join(timeout=30)
            if self._proc.is_alive():
                self._proc.terminate()
                self._proc.join(timeout=10)
            self._proc = None

    def _call(self, cmd: str, payload=None):
        self._conn.send((cmd, payload))
        status, out = self._conn.recv()
        if status != "ok":
            raise RemoteReplicaError(f"{self.replica_id}: {cmd}: {out}")
        return out

    # -- replica protocol (cached routing state, RPC serving) ---------------
    @property
    def epoch(self) -> int:
        return self._state["epoch"]

    @property
    def healthy(self) -> bool:
        return self._state["healthy"]

    @property
    def missing_docs(self) -> int:
        return self._state["missing_docs"]

    @property
    def gen(self) -> int:
        return self._state["gen"]

    @property
    def platform(self):
        """The JAX platform the child serves on (None before its first
        state-changing call)."""
        return self._state["platform"]

    def sync_once(self):
        out, self._state = self._call("sync")
        return out

    def collection_stats(self):
        return self._call("stats")

    def install_stats(self, stats) -> None:
        self._call("install_stats", stats)

    def query_max_ub(self, q2d):
        return self._call("ub", np.asarray(q2d))

    def search_batched(self, q_batch, k: int = 10, theta0=None):
        t = None if theta0 is None else np.asarray(theta0)
        return self._call("search", (np.asarray(q_batch), int(k), t))

    def quarantine(self, file_name: str):
        out, self._state = self._call("quarantine", file_name)
        return out

    def repair(self, base: str):
        out, self._state = self._call("repair", base)
        return out

    def anti_entropy(self):
        out, self._state = self._call("anti_entropy")
        return out

    def refresh_state(self) -> dict:
        self._state = self._call("state")
        return dict(self._state)

    def report(self) -> dict:
        return self._call("report")
