"""Coordinator metadata replication: the stream a warm standby adopts.

The coordinator (``repro.core.shard.ShardedEngine``) is the single owner of
everything a ``selection_state()`` snapshot does not cover: the clustered
table lineage, per-shard delta logs and checkpoint watermarks, the sketch
index's registrations, and the fragment placement.  One coordinator SIGKILL
used to lose all of it — every captured sketch would have to be re-captured,
defeating the paper's premise that a sketch keeps paying for itself.

This module streams every coordinator **metadata mutation** as a
monotonically-sequenced :class:`ReplicationRecord` to a replica, which folds
the stream into a :class:`MetadataStore` — exactly the state a standby needs
to call ``ShardedEngine.from_replica`` and resume serving:

* ``bootstrap`` — the full base state: clustered table, dims, ranges,
  placement, engine construction kwargs, current delta logs.  Emitted once
  at ``attach_replica`` time (and again by a freshly-promoted coordinator to
  re-arm its own standby).
* ``mutation`` — one ``append_rows``/``delete_rows``, with the *original*
  coordinator-order payload (so replay reproduces the exact row order the
  recorded delete masks index into) plus the per-shard ship payloads (so the
  standby's delta logs can re-ship anything a shard never drained).
* ``register`` / ``evict`` — sketch-index registrations keyed by the stable
  ``reg_id`` the shards also key their maintainers by.  Only the query,
  ranges and locality flag travel: sketch *bits* are never replicated — the
  standby re-derives them by local counting (``maintainer_for``), the same
  "maintain, don't re-capture" rule shard recovery follows.
* ``ckpt`` — a shard checkpoint advanced to some version; prunes the
  replica's copy of that shard's delta log.
* ``selection`` — a ``selection_state()`` snapshot (WorkloadLog window +
  SelectionCache stats), emitted at metadata flush points.  Bounded
  staleness here can only shift future *selection* decisions, never query
  results (sketches are lossless).
* ``plan`` — a rebalance re-placement (new owner array + voided shards).

Two replicas share the stream format: :class:`InProcessReplica` folds
records in the coordinator's process (zero-copy; the loopback analogue),
:class:`SubprocessReplica` ships them over ``runtime/transport`` frames to a
warm standby process (``python -m repro.core.replication``) that survives
the coordinator's death and hands the store back at takeover.

A sequence gap raises :class:`ReplicationError` at the replica — a standby
must refuse to take over from a stream it knows is missing records.
"""
from __future__ import annotations

import atexit
import dataclasses
import itertools
import os
import socket
import subprocess
import sys
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.runtime import transport

RECORD_KINDS = ("bootstrap", "mutation", "register", "evict", "ckpt",
                "selection", "plan")


class ReplicationError(RuntimeError):
    """The replication stream is unusable (gap, unknown record, dead
    standby) — the coordinator degrades to unreplicated, never crashes."""


@dataclasses.dataclass(frozen=True)
class ReplicationRecord:
    """One monotonically-sequenced metadata mutation."""

    seq: int
    kind: str
    payload: object


class MetadataStore:
    """A replica's folded view of the coordinator metadata stream.

    Everything ``ShardedEngine.from_replica`` needs: the bootstrap base,
    the coordinator-order mutation log to replay on it, per-shard delta-log
    suffixes, the ordered registration set, the latest selection snapshot,
    and the current placement.
    """

    def __init__(self):
        self.boot: Optional[dict] = None
        # Coordinator-order mutations since bootstrap: (kind, table, payload,
        # version) with version None for dimension-table mutations (they do
        # not advance the serving watermark).
        self.muts: List[Tuple[str, str, object, Optional[int]]] = []
        self._logs: Dict[int, List[Tuple[int, str, object]]] = {}
        self.ckpt_versions: Dict[int, Optional[int]] = {}
        # reg_id -> registration payload, insertion-ordered (dict semantics):
        # index insertion order must replay identically or lookup ties could
        # resolve differently on the standby.
        self.regs: Dict[int, dict] = {}
        self.selection: Optional[dict] = None
        self.owner: Optional[np.ndarray] = None
        self.version = 0
        self.reg_counter = 1
        self.last_seq = 0

    def apply(self, rec: ReplicationRecord) -> None:
        if rec.seq != self.last_seq + 1:
            raise ReplicationError(
                f"replication gap: record seq {rec.seq} after {self.last_seq}")
        self.last_seq = rec.seq
        kind = rec.kind
        if kind == "bootstrap":
            p = dict(rec.payload)
            self.boot = p
            self.owner = np.asarray(p["owner"])
            self.version = int(p["version"])
            self.muts = []
            self._logs = {s: list(entries)
                          for s, entries in enumerate(p.get("log") or [])}
            self.ckpt_versions = dict(
                enumerate(p.get("ckpt_versions") or []))
            self.regs = {}
            self.reg_counter = int(p.get("reg_counter", 1))
            self.selection = p.get("selection")
        elif kind == "mutation":
            mkind, tname, payload, version, ships = rec.payload
            self.muts.append((mkind, tname, payload, version))
            if version is not None:
                self.version = int(version)
                for sid, sp in enumerate(ships or ()):
                    self._logs.setdefault(sid, []).append(
                        (int(version), mkind, sp))
        elif kind == "register":
            for p in rec.payload:
                rid = int(p["reg_id"])
                self.regs[rid] = dict(p)
                self.reg_counter = max(self.reg_counter, rid + 1)
        elif kind == "evict":
            self.regs.pop(int(rec.payload), None)
        elif kind == "ckpt":
            sid, v = rec.payload
            self.ckpt_versions[int(sid)] = v
            log = self._logs.get(int(sid))
            if log and v is not None:
                self._logs[int(sid)] = [e for e in log if e[0] > v]
        elif kind == "selection":
            self.selection = rec.payload
        elif kind == "plan":
            owner, voided = rec.payload
            self.owner = np.asarray(owner)
            for sid in voided:
                self._logs[int(sid)] = []
                self.ckpt_versions[int(sid)] = None
        else:
            raise ReplicationError(f"unknown record kind {kind!r}")

    def ship_logs(self, n_shards: int) -> List[List[Tuple[int, str, object]]]:
        return [list(self._logs.get(s, ())) for s in range(n_shards)]


class InProcessReplica:
    """Warm-standby metadata held in the same process — the loopback
    analogue of :class:`SubprocessReplica` (identical record stream and
    takeover surface, zero serialization)."""

    backend = "loopback"

    def __init__(self):
        self._store = MetadataStore()
        self.records = 0

    def publish(self, rec: ReplicationRecord) -> None:
        self._store.apply(rec)
        self.records += 1

    def snapshot(self) -> MetadataStore:
        return self._store

    # ``close_replica`` (not ``close``): keeps the hot-path analyzer's
    # name-based call graph from aliasing socket ``close()`` calls in the RPC
    # hot path onto replica teardown (which reaches ``Popen.wait``).
    def close_replica(self) -> None:
        pass


# ---------------------------------------------------------------------------
# Subprocess replica: a standby process that outlives the coordinator object
# ---------------------------------------------------------------------------

_SPAWN_TIMEOUT_S = 60.0
_sock_counter = itertools.count(1)
_live_replicas: "set[SubprocessReplica]" = set()


def _kill_live_replicas() -> None:
    for r in list(_live_replicas):
        r.close_replica()


atexit.register(_kill_live_replicas)


class SubprocessReplica:
    """Streams replication records to a warm standby process over the same
    framed transport the shard RPC uses (crc-checked, deadline-bounded).

    The child applies each record into its own :class:`MetadataStore`;
    ``snapshot()`` pulls the folded store back — the takeover path.  The
    child watches its stdin pipe and exits when the parent dies, and every
    spawned replica is killed ``atexit``, so standbys never orphan.
    """

    backend = "subprocess"

    def __init__(self, deadline_s: float = 30.0):
        self._deadline_s = deadline_s
        self._seq = itertools.count(1)
        self.records = 0
        from repro.core.shard_rpc import _socket_dir, child_env

        self.path = os.path.join(_socket_dir(),
                                 f"r{next(_sock_counter)}.sock")
        # NOT ``-m repro.core.replication``: running the module as __main__
        # would make the standby's MetadataStore pickle as
        # ``__main__.MetadataStore`` and fail to unpickle at takeover.
        self.proc: Optional[subprocess.Popen] = subprocess.Popen(
            [sys.executable, "-c",
             "import sys; from repro.core.replication import main; "
             "main(sys.argv[1:])", self.path],
            stdin=subprocess.PIPE, stdout=subprocess.DEVNULL,
            start_new_session=True, env=child_env())
        self.conn: Optional[socket.socket] = None
        _live_replicas.add(self)

    def _connect(self) -> None:
        import time as _time

        t_end = _time.perf_counter() + _SPAWN_TIMEOUT_S
        last: Optional[Exception] = None
        while _time.perf_counter() < t_end:
            if self.proc is None or self.proc.poll() is not None:
                raise ReplicationError("standby process exited")
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            s.settimeout(1.0)
            try:
                s.connect(self.path)
                self.conn = s
                return
            except (FileNotFoundError, ConnectionRefusedError,
                    socket.timeout, OSError) as e:
                last = e
                s.close()
                _time.sleep(0.02)
        raise ReplicationError(f"could not connect to standby: {last}")

    def _call(self, msg: dict):
        if self.proc is None:
            raise ReplicationError("replica closed")
        if self.conn is None:
            self._connect()
        seq = next(self._seq)
        try:
            transport.send_msg(self.conn, msg, seq,
                               deadline_s=self._deadline_s)
            rseq, resp = transport.recv_msg(self.conn,
                                            deadline_s=self._deadline_s)
        except transport.TransportError as e:
            raise ReplicationError(f"standby rpc failed: {e}") from e
        if rseq != seq or not resp.get("ok"):
            raise ReplicationError(
                f"standby refused {msg.get('op')}: {resp.get('msg', 'desync')}")
        return resp.get("value")

    def publish(self, rec: ReplicationRecord) -> None:
        self._call({"op": "publish", "rec": rec})
        self.records += 1

    def snapshot(self) -> MetadataStore:
        return self._call({"op": "snapshot"})

    def close_replica(self) -> None:
        proc, self.proc = self.proc, None
        _live_replicas.discard(self)
        if self.conn is not None:
            try:
                self.conn.close()
            except OSError:
                pass
            self.conn = None
        if proc is not None:
            try:
                proc.kill()
                proc.wait(timeout=5.0)
            except Exception:
                pass


# ---------------------------------------------------------------------------
# Standby server entry (python -m repro.core.replication <socket-path>)
# ---------------------------------------------------------------------------


def serve(path: str) -> None:
    """The standby loop: fold published records, hand the store back on
    ``snapshot``.  Reconnect-tolerant like the shard server — the folded
    store survives a dropped coordinator connection (that is the point)."""
    def _watchdog():
        try:
            while True:
                if not sys.stdin.buffer.read(4096):
                    break
        except Exception:
            pass
        os._exit(2)

    threading.Thread(target=_watchdog, daemon=True).start()
    store = MetadataStore()
    closed = False
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    sock.bind(path)
    sock.listen(2)
    while not closed:
        conn, _ = sock.accept()
        try:
            while not closed:
                seq, msg = transport.recv_msg(conn, deadline_s=None)
                op = msg.get("op")
                try:
                    if op == "publish":
                        store.apply(msg["rec"])
                        resp = {"ok": True, "value": None}
                    elif op == "snapshot":
                        resp = {"ok": True, "value": store}
                    elif op == "ping":
                        resp = {"ok": True, "value": "pong"}
                    elif op == "shutdown":
                        closed = True
                        resp = {"ok": True, "value": None}
                    else:
                        resp = {"ok": False, "msg": f"unknown op {op!r}"}
                except Exception as e:
                    resp = {"ok": False, "msg": f"{type(e).__name__}: {e}"}
                transport.send_msg(conn, resp, seq)
        except (transport.RpcClosed, transport.FrameError, OSError):
            pass  # coordinator died or reconnected; keep the store
        finally:
            try:
                conn.close()
            except OSError:
                pass
    os._exit(0)


def main(argv: Optional[List[str]] = None) -> None:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print("usage: python -m repro.core.replication <socket-path>",
              file=sys.stderr)
        raise SystemExit(2)
    serve(args[0])


if __name__ == "__main__":
    main()
