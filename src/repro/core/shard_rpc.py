"""Real process-boundary shards: RPC clients, shard server, process pool.

``repro.core.shard`` splits the shard surface in two:

* **ShardClient** (this module): the coordinator-side handle.  Two
  interchangeable backends — :class:`LoopbackShardClient` wraps an
  in-process ``FragmentShard`` (today's zero-copy behavior; every existing
  test runs unchanged on it), :class:`SubprocessShardClient` talks to a
  ``FragmentShard`` living in a separate OS process over a unix-socket RPC
  channel (``repro.runtime.transport``).  Both expose the same op surface,
  and both speak the serving layer's failure vocabulary: an RPC timeout or
  a dead connection surfaces as ``ShardUnavailableError``, a full inbox as
  ``BackpressureError`` — so the PR 6 health machine, ``rebalance()``, and
  degraded routing run unchanged on top of *real* process failures.

* **ShardServer** (this module, run via ``python -m repro.core.shard_rpc``):
  the shard-side loop.  Owns one ``FragmentShard``, drains its inbox,
  applies deltas, and serves registration / sketch-bit / partial-aggregate
  ops.  Fault injection maps to real mechanisms: ``kill`` is a SIGKILL of
  the server process, ``stall`` a server-side sleep per op, ``partition`` a
  client-side socket drop, ``flaky`` server-injected RPC error responses —
  the same ``runtime/chaos.py`` schedules that drove in-process flags now
  drive genuine process death and socket failures.

Checkpoints cross the boundary differently per backend
(:class:`ShardCheckpoint`): loopback keeps a zero-copy reference to the
shard's immutable local table; the subprocess backend snapshots the
*coordinator's* clustered table at the checkpoint watermark (tables are
immutable, so the reference IS the snapshot) and recovery rebuilds the
shard server-side from it — deterministic because ``FragmentShard``
construction from (table, plan, ranges, version) is a pure function — then
replays the delta log and re-registers maintainers, never re-captures.

The warm read path stays at ~1 RPC per shard per read: ``catch_up``
responses piggyback the shard's state token, maintainer keys, dimension
tokens, and maintained sketch bits, which the client caches until its own
next state-changing op (all mutation flows through the client, so the
cache cannot go stale silently).
"""
from __future__ import annotations

import atexit
import dataclasses
import itertools
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Mapping, Optional, Set, Tuple

import numpy as np

from repro.core.maintenance import MaintenanceError
from repro.core.queries import Query, inner_block_arrays
from repro.core.ranges import RangeSet
from repro.core.table import ColumnTable
from repro.runtime import transport
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.guards import hot_path

# Imported lazily where needed to keep `python -m repro.core.shard_rpc`
# startup lean; shard.py never imports this module at module level, so the
# one-way top-level import below is cycle-free.
from repro.core.shard import (  # noqa: E402
    BackpressureError,
    FragmentShard,
    ShardPlan,
    ShardUnavailableError,
    StaleEpochError,
)


# ---------------------------------------------------------------------------
# Shared client-side value types
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardCheckpoint:
    """One shard's durable recovery point.

    ``kind == "local"``: ``table`` is the shard's own immutable local table
    (loopback; adopt is zero-copy).  ``kind == "coord"``: ``table`` is the
    coordinator's clustered table at the checkpoint watermark; recovery
    rebuilds the shard from it server-side (the subprocess backend — the
    coordinator cannot cheaply read a remote shard's table, but it *can*
    reconstruct it deterministically).
    """

    kind: str  # "local" | "coord"
    table: ColumnTable
    version: int


@dataclasses.dataclass(frozen=True)
class _EncView:
    """Client-side stand-in for ``catalog.GroupEncoding`` built from a
    server's ``block_arrays`` response (only the fields the stacked-layout
    builder reads)."""

    n_groups: int
    group_values: Dict[str, np.ndarray]
    gid: np.ndarray


#: Server exception type name -> local class, for re-raising RPC errors as
#: the types the serving layer's retry/health logic dispatches on.
_ERROR_TYPES = {
    "ShardUnavailableError": ShardUnavailableError,
    "BackpressureError": BackpressureError,
    "MaintenanceError": MaintenanceError,
    "StaleEpochError": StaleEpochError,
    "ValueError": ValueError,
    "KeyError": KeyError,
    "RuntimeError": RuntimeError,
}


def _raise_remote(resp: dict) -> None:
    etype, msg = resp.get("etype", "RuntimeError"), resp.get("msg", "")
    cls = _ERROR_TYPES.get(etype)
    if cls is None:
        raise transport.RemoteError(etype, msg)
    raise cls(msg)


# ---------------------------------------------------------------------------
# Loopback client (in-process, zero-copy — today's behavior)
# ---------------------------------------------------------------------------


class LoopbackShardClient:
    """In-process backend: wraps a ``FragmentShard`` directly.

    Everything not defined here delegates to the wrapped shard, so tests
    (and the chaos harness) that poke shard internals — ``maintainers``,
    ``dims``, ``catch_up``, ``inject``/``heal``, ``table`` — behave exactly
    as before the client split.
    """

    backend = "loopback"

    def __init__(self, shard: FragmentShard):
        self._shard = shard
        # This client's coordinator epoch, stamped on every state-touching
        # op (the loopback analog of the RPC payload's epoch field).  The
        # owning ``ShardedEngine`` sets it; a takeover wraps the same
        # ``FragmentShard`` in a *new* client carrying the bumped epoch,
        # after which this client's ops are fenced out.
        self.epoch = 0

    def __getattr__(self, name):
        if name == "_shard":  # during unpickling/partial init
            raise AttributeError(name)
        return getattr(self._shard, name)

    def _fence(self, op: str) -> None:
        """Stamp/check this client's epoch on the shard before a fenced op.

        Skipped while the shard is unreachable: the op itself raises
        ``ShardUnavailableError`` at the fault guard, and a partitioned
        zombie must not be able to *bump* the shard's epoch through the
        partition (nor learn it was fenced — it can't reach the shard)."""
        if self._shard.fault in ("dead", "partition"):
            return
        self._shard.fence(self.epoch, op)

    # -- fenced state-touching ops (otherwise delegated via __getattr__) ------
    def ship(self, version: int, kind: str, payload) -> None:
        self._fence("ship")
        self._shard.ship(version, kind, payload)

    def catch_up(self, watermark: int) -> int:
        self._fence("catch_up")
        return self._shard.catch_up(watermark)

    def register(self, key: int, q: Query, ranges: RangeSet) -> None:
        self._fence("register")
        self._shard.register(key, q, ranges)

    def update_dim(self, table: ColumnTable) -> None:
        self._fence("update_dim")
        self._shard.update_dim(table)

    def bits_for(self, key: int) -> Optional[np.ndarray]:
        self._fence("bits_for")
        return self._shard.bits_for(key)

    def partial(self, q: Query, key: int, ranges: RangeSet,
                bits: np.ndarray):
        self._fence("partial")
        return self._shard.partial(q, key, ranges, bits)

    def clone_for_takeover(self) -> "LoopbackShardClient":
        """A fresh client over the SAME live shard for a takeover
        coordinator — shard state (table, maintainers, epoch) stays put;
        only the client-side identity is new."""
        return LoopbackShardClient(self._shard)

    # -- client-only surface (the API ``ShardedEngine`` is written against)
    def block_arrays(self, key: int, ranges: RangeSet, bits: np.ndarray,
                     q: Query):
        """One shard's inner-block arrays for the stacked layout."""
        self._fence("block_arrays")
        shard = self._shard
        inst = shard._instance(key, ranges, bits)
        if q.join is not None:
            flat, _ = shard.catalog.join(
                inst, shard.dims[q.join.right], q.join.left_key,
                q.join.right_key)
        else:
            flat = inst
        return inner_block_arrays(q, flat, shard.catalog)

    def has_maintainer(self, key: int) -> bool:
        return key in self._shard.maintainers

    def dim_token(self, name: str) -> Optional[Tuple[int, int]]:
        t = self._shard.dims.get(name)
        return None if t is None else (t.uid, t.version)

    def state_token(self) -> Optional[Tuple[int, int]]:
        t = self._shard.table
        return None if t is None else (t.uid, t.version)

    @property
    def state_lost(self) -> bool:
        return self._shard.table is None

    def make_checkpoint(self, coord_table: ColumnTable,
                        coord_version: int) -> ShardCheckpoint:
        t = self._shard.table
        return ShardCheckpoint(kind="local", table=t, version=t.version)

    def restore_checkpoint(self, ckpt: ShardCheckpoint,
                dims: Mapping[str, ColumnTable], plan: ShardPlan,
                ranges: RangeSet) -> None:
        self._fence("restore_checkpoint")
        self._shard.adopt(ckpt.table, dims)

    def rebuild(self, plan: ShardPlan, ranges: RangeSet,
                clustered: ColumnTable, dims: Mapping[str, ColumnTable],
                device, inbox_cap: Optional[int], version: int) -> None:
        self._fence("rebuild")
        epoch = self._shard.epoch
        self._shard = FragmentShard(
            self._shard.shard_id, plan, ranges, clustered, dims, device,
            inbox_cap=inbox_cap, version=version)
        # Epoch is process identity, not table state: it survives the
        # rebuild, so a fenced-out coordinator stays fenced out.
        self._shard.epoch = epoch

    def close_client(self) -> None:
        pass


# ---------------------------------------------------------------------------
# Server process pool
# ---------------------------------------------------------------------------

_SPAWN_TIMEOUT_S = 60.0
_sock_counter = itertools.count(1)
_sock_dir: Optional[str] = None


def _socket_dir() -> str:
    global _sock_dir
    if _sock_dir is None:
        _sock_dir = tempfile.mkdtemp(prefix="repro-shards-")
    return _sock_dir


def child_env() -> Dict[str, str]:
    """Environment for a spawned shard server or standby.

    Children import this package from the parent's source tree and run on
    the CPU: a chip belongs to one process, and on a TPU host the parent
    (the coordinator) already holds it, so a child that tried to load the
    TPU runtime would fail or hang.  Subprocess shards are CPU-side by
    design; the chip path is the one-process ``shard_map`` launch.
    """
    env = dict(os.environ)
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


class _ServerProc:
    """One shard server subprocess + its RPC connection."""

    def __init__(self, proc: subprocess.Popen, path: str):
        self.proc = proc
        self.path = path
        self.conn: Optional[socket.socket] = None
        self._seq = itertools.count(1)

    @property
    def alive(self) -> bool:
        return self.proc.poll() is None

    def connect(self, deadline_s: float = _SPAWN_TIMEOUT_S) -> None:
        """(Re)connect to the server's listening socket, waiting out the
        child's interpreter/jax startup on first contact."""
        self.drop_conn()
        t_end = time.perf_counter() + deadline_s
        last: Optional[Exception] = None
        while time.perf_counter() < t_end:
            if not self.alive:
                raise ShardUnavailableError(
                    f"shard server {self.proc.pid} exited "
                    f"(rc={self.proc.poll()})")
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
                time.sleep(0.02)
        raise ShardUnavailableError(
            f"could not connect to shard server at {self.path}: {last}")

    def drop_conn(self) -> None:
        if self.conn is not None:
            try:
                self.conn.close()
            except OSError:
                pass
            self.conn = None

    def request(self, payload: dict, deadline_s: float) -> dict:
        """One request/response exchange.  Transport failures surface as
        ``ShardUnavailableError`` — the retryable class of the serving
        layer — after dropping the (now desynced) connection."""
        if self.conn is None:
            self.connect(deadline_s=max(deadline_s, 10.0))
        seq = next(self._seq)
        try:
            transport.send_msg(self.conn, payload, seq, deadline_s=deadline_s)
            rseq, resp = transport.recv_msg(self.conn, deadline_s=deadline_s)
        except transport.RpcTimeout as e:
            self.drop_conn()
            raise ShardUnavailableError(
                f"rpc {payload.get('op')} timed out: {e}") from e
        except (transport.RpcClosed, transport.FrameError, OSError) as e:
            self.drop_conn()
            raise ShardUnavailableError(
                f"rpc {payload.get('op')} connection lost: {e}") from e
        if rseq != seq:
            self.drop_conn()
            raise ShardUnavailableError(
                f"rpc desync (sent seq {seq}, got {rseq})")
        return resp

    def kill(self) -> None:
        try:
            self.proc.kill()
        except OSError:
            pass
        self.drop_conn()
        try:
            self.proc.wait(timeout=5.0)
        except Exception:
            pass


class ServerPool:
    """Reusable shard-server subprocesses.

    Spawning a server pays the child's interpreter + jax import (~1s); at
    100+ chaos replays that cost would dominate everything.  The pool keeps
    *stateless* warm servers (a ``reset`` op drops the shard between
    tenants but keeps the process and its XLA compile caches alive) and
    tops up a small spare set in the background so a post-kill respawn
    usually pops a warm process instead of cold-starting one.

    Orphan safety is layered: every spawned pid is tracked and SIGKILLed
    ``atexit``; each child also watches its stdin pipe and exits the moment
    the parent dies (EOF) — so neither a crashed test run nor a killed
    coordinator leaks shard servers.
    """

    def __init__(self, spares: Optional[int] = None):
        self._lock = threading.Lock()
        self._spares: List[_ServerProc] = []
        self._all: Set[_ServerProc] = set()
        self._target = (int(os.environ.get("REPRO_SHARD_SPARES", "2"))
                        if spares is None else spares)
        self._filling = False
        self._closed = False

    def _spawn(self) -> _ServerProc:
        # Checked twice: before paying the Popen, and again before tracking
        # the child — a close_pool() racing this spawn (atexit vs the background
        # top-up thread) must never leave an untracked orphan behind.
        with self._lock:
            if self._closed:
                raise ShardUnavailableError("server pool is closed")
        path = os.path.join(_socket_dir(), f"s{next(_sock_counter)}.sock")
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.core.shard_rpc", path],
            stdin=subprocess.PIPE, stdout=subprocess.DEVNULL,
            start_new_session=True, env=child_env())
        sp = _ServerProc(proc, path)
        with self._lock:
            if not self._closed:
                self._all.add(sp)
                return sp
        sp.kill()
        raise ShardUnavailableError("server pool closed mid-spawn")

    def acquire(self) -> _ServerProc:
        with self._lock:
            sp = self._spares.pop() if self._spares else None
        while sp is not None and not sp.alive:
            with self._lock:
                self._all.discard(sp)
                sp = self._spares.pop() if self._spares else None
        if sp is None:
            sp = self._spawn()
        self._top_up_async()
        return sp

    def release(self, sp: _ServerProc) -> None:
        """Return a server to the spare set (after a state reset), or reap
        it if it is no longer serviceable."""
        if not sp.alive:
            self.discard(sp)
            return
        try:
            resp = sp.request({"op": "reset", "args": (), "ctl": True},
                              deadline_s=10.0)
            if not resp.get("ok"):
                raise ShardUnavailableError("reset refused")
        except ShardUnavailableError:
            self.discard(sp)
            return
        with self._lock:
            self._spares.append(sp)

    def discard(self, sp: _ServerProc) -> None:
        sp.kill()
        with self._lock:
            self._all.discard(sp)
            if sp in self._spares:
                self._spares.remove(sp)

    def prewarm(self, n: int) -> None:
        """Synchronously grow the spare set to ``n`` (bench warmup hook)."""
        need = []
        with self._lock:
            cur = len(self._spares)
        for _ in range(max(0, n - cur)):
            need.append(self._spawn())
        with self._lock:
            self._spares.extend(need)

    def _top_up_async(self) -> None:
        with self._lock:
            if self._filling or len(self._spares) >= self._target:
                return
            self._filling = True

        def fill():
            try:
                while True:
                    with self._lock:
                        if self._closed or len(self._spares) >= self._target:
                            return
                    try:
                        sp = self._spawn()
                    except ShardUnavailableError:
                        return  # pool closed mid-fill
                    with self._lock:
                        if self._closed:
                            break
                        self._spares.append(sp)
                sp.kill()
            finally:
                with self._lock:
                    self._filling = False

        threading.Thread(target=fill, daemon=True).start()

    def _drain(self) -> List[_ServerProc]:
        with self._lock:
            self._closed = True
            procs = list(self._all)
            self._all.clear()
            self._spares.clear()
        return procs

    def shutdown_all(self) -> None:
        """Kill every pooled server, then reopen for the next tenant (bench
        suites reuse the module-level pool across scenarios).  The closed
        window is what makes this race-free against the background top-up
        thread: a spawn landing mid-shutdown is killed, not leaked."""
        procs = self._drain()
        for sp in procs:
            sp.kill()
        with self._lock:
            self._closed = False

    # Named close_pool (not ``close``) so the hot-path analyzer's name-based
    # call graph cannot alias socket ``close()`` calls in the RPC hot path
    # onto this terminal teardown (which reaches Popen.wait).
    def close_pool(self) -> None:
        """Terminal shutdown (atexit): kill everything and stay closed so
        no late daemon-thread spawn can outlive the coordinator."""
        for sp in self._drain():
            sp.kill()


#: Process-wide pool; ``atexit`` guarantees no shard server outlives the
#: coordinator process even when tests die mid-run.
POOL = ServerPool()
atexit.register(POOL.close_pool)


# ---------------------------------------------------------------------------
# Subprocess client
# ---------------------------------------------------------------------------


class SubprocessShardClient:
    """Coordinator-side handle for a shard living in its own OS process.

    Failure semantics are genuine: ``kill`` SIGKILLs the server process
    (heal respawns an *empty* one — state is really gone until the
    coordinator runs checkpoint-rebuild + delta-replay + re-registration),
    ``partition`` drops the socket client-side with server state intact,
    ``stall`` makes the server sleep per op (past the RPC deadline it
    surfaces as a timeout), ``flaky`` makes the server fail the next N ops
    with marshalled errors that exercise the retry path over real RPC.
    """

    backend = "subprocess"

    def __init__(
        self,
        shard_id: int,
        plan: ShardPlan,
        ranges: RangeSet,
        clustered: ColumnTable,
        dims: Mapping[str, ColumnTable],
        inbox_cap: Optional[int] = None,
        version: int = 0,
        op_deadline_s: float = 5.0,
        pool: Optional[ServerPool] = None,
    ):
        self.shard_id = shard_id
        self._pool = pool or POOL
        self._inbox_cap = inbox_cap
        # RPC deadline: comfortably past the engine's op deadline so a
        # mild stall completes slowly (straggler semantics, like loopback)
        # while a hard stall still times out into ShardUnavailableError.
        self._deadline_s = max(op_deadline_s * 2.0, op_deadline_s + 1.0)
        self._build_deadline_s = max(120.0, self._deadline_s)
        self._proc: Optional[_ServerProc] = self._pool.acquire()
        self._fault: Optional[str] = None  # None|"dead"|"partition"|"stall"
        self._state_lost = True
        self._version = -1
        self._lag = 0
        self._bp = 0
        self._token: Optional[Tuple[int, int]] = None
        self._mkeys: Set[int] = set()
        self._bits: Optional[Dict[int, np.ndarray]] = None
        self._dims: Dict[str, Tuple[int, int]] = {}
        self._pending_unregister: Set[int] = set()
        # Coordinator epoch stamped on every non-ctl request; the server
        # fences ops behind the newest epoch it has seen (set by the owning
        # ``ShardedEngine`` — 0 only during this initial build).
        self.epoch = 0
        self._build(plan, ranges, clustered, dims, version)

    # -- plumbing --------------------------------------------------------------
    def _absorb_meta(self, meta: Optional[dict]) -> None:
        if not meta:
            return
        self._version = meta["version"]
        self._lag = meta["lag"]
        self._bp = meta["bp"]
        self._token = meta["token"]
        self._mkeys = set(meta["mkeys"])
        self._dims = dict(meta["dims"])
        if "bits" in meta:
            self._bits = meta["bits"]

    def _request(self, op: str, args: tuple, ctl: bool = False,
                 deadline_s: Optional[float] = None):
        if not ctl and self._fault == "partition":
            raise ShardUnavailableError(
                f"shard {self.shard_id} is partition ({op})")
        if self._proc is None:
            raise ShardUnavailableError(
                f"shard {self.shard_id} is dead ({op})")
        resp = self._proc.request(
            {"op": op, "args": args, "ctl": ctl, "epoch": self.epoch},
            deadline_s=self._deadline_s if deadline_s is None else deadline_s)
        self._absorb_meta(resp.get("meta"))
        if not resp.get("ok"):
            _raise_remote(resp)
        return resp.get("value")

    def _build(self, plan: ShardPlan, ranges: RangeSet,
               clustered: ColumnTable, dims: Mapping[str, ColumnTable],
               version: int) -> None:
        # Collapse before shipping: the wire must carry one table's columns,
        # not its whole delta-chain history.
        self._request(
            "build",
            (self.shard_id, plan.owner, plan.n_shards, ranges,
             clustered.collapse(),
             {k: v.collapse() for k, v in dims.items()},
             self._inbox_cap, version, self.shard_id),
            deadline_s=self._build_deadline_s)
        self._state_lost = False
        self._bits = self._bits if self._bits is not None else {}

    def _flush_unregisters(self) -> None:
        if not self._pending_unregister:
            return
        keys = tuple(self._pending_unregister)
        try:
            self._request("unregister_many", (keys,), ctl=True)
            self._pending_unregister.clear()
        except ShardUnavailableError:
            pass  # still unreachable; retry on a later op

    # -- fault injection (chaos surface) ---------------------------------------
    def inject(self, kind: str, arg=None) -> None:
        """Real-mechanism fault injection (see class docstring)."""
        if kind == "kill":
            if self._proc is not None:
                self._pool.discard(self._proc)
                self._proc = None
            self._fault = "dead"
            self._state_lost = True
            self._version = -1
            self._lag = 0
            self._token = None
            self._mkeys = set()
            self._bits = None
            self._dims = {}
            self._pending_unregister.clear()
        elif kind == "stall":
            s = float(arg) if arg is not None else 0.02
            self._request("set_stall", (s,), ctl=True)
            self._fault = "stall"
        elif kind == "partition":
            self._fault = "partition"
            if self._proc is not None:
                self._proc.drop_conn()
        elif kind == "flaky":
            self._request("set_flaky",
                          (int(arg) if arg is not None else 1,), ctl=True)
        else:
            raise ValueError(f"unknown fault kind {kind!r}")

    def heal(self) -> None:
        """Clear the fault.  After a kill this respawns a *fresh, empty*
        server (from the warm pool when possible): the shard is reachable
        again but its state is genuinely lost until the coordinator runs
        checkpoint-rebuild + delta-replay + re-registration."""
        if self._proc is None or not self._proc.alive:
            if self._proc is not None:
                self._pool.discard(self._proc)
            self._proc = self._pool.acquire()
            self._state_lost = True
            self._version = -1
            self._token = None
            self._mkeys = set()
            self._bits = None
            self._dims = {}
        elif self._fault == "stall" or self._fault is None:
            try:
                self._request("clear_faults", (), ctl=True)
            except ShardUnavailableError:
                pass
        self._fault = None

    @property
    def reachable(self) -> bool:
        return self._fault not in ("dead", "partition")

    # -- replication -----------------------------------------------------------
    @property
    def version(self) -> int:
        return -1 if self._state_lost else self._version

    @property
    def lag(self) -> int:
        return self._lag

    @property
    def backpressure_hits(self) -> int:
        return self._bp

    @property
    def inbox_cap(self) -> Optional[int]:
        return self._inbox_cap

    def ship(self, version: int, kind: str, payload) -> None:
        self._request("ship", (version, kind, payload))

    def catch_up(self, watermark: int) -> int:
        self._flush_unregisters()
        return self._request("catch_up", (watermark,))

    def update_dim(self, table: ColumnTable) -> None:
        self._request("update_dim", (table.collapse(),))

    def dim_token(self, name: str) -> Optional[Tuple[int, int]]:
        return self._dims.get(name)

    # -- sketch registration ---------------------------------------------------
    def register(self, key: int, q: Query, ranges: RangeSet) -> None:
        self._flush_unregisters()
        self._request("register", (key, q, ranges))

    def unregister(self, key: int) -> None:
        # Best-effort, like the loopback (whose unregister has no fault
        # guard): an unreachable shard's stale maintainer is queued and
        # flushed before the next register/catch_up, so a recycled entry
        # id can never alias onto it.
        self._mkeys.discard(key)
        if self._bits is not None:
            self._bits.pop(key, None)
        try:
            self._request("unregister_many", ((key,),), ctl=True)
        except ShardUnavailableError:
            self._pending_unregister.add(key)

    def has_maintainer(self, key: int) -> bool:
        return key in self._mkeys

    def bits_for(self, key: int) -> Optional[np.ndarray]:
        if self._fault in ("dead", "partition"):
            raise ShardUnavailableError(
                f"shard {self.shard_id} is {self._fault} (bits_for)")
        if self._state_lost:
            raise ShardUnavailableError(
                f"shard {self.shard_id} lost its state (bits_for)")
        if self._bits is not None:
            # Piggybacked on the last catch_up/register response; every
            # bit-changing op flows through this client, so the cache is
            # exact — the warm read path pays zero extra RPCs here.
            return self._bits.get(key)
        return self._request("bits_for", (key,))

    # -- query serving ---------------------------------------------------------
    @hot_path
    def partial(self, q: Query, key: int, ranges: RangeSet,
                bits: np.ndarray) -> Tuple[Dict[str, np.ndarray],
                                           np.ndarray, np.ndarray]:
        return self._request("partial", (q, key, ranges, np.asarray(bits)))

    @hot_path
    def block_arrays(self, key: int, ranges: RangeSet, bits: np.ndarray,
                     q: Query):
        n_groups, group_values, gid, where, vals = self._request(
            "block_arrays", (key, ranges, np.asarray(bits), q))
        return (_EncView(n_groups=n_groups, group_values=group_values,
                         gid=gid), where, vals)

    # -- state identity / recovery ---------------------------------------------
    def state_token(self) -> Optional[Tuple[int, int]]:
        return None if self._state_lost else self._token

    @property
    def state_lost(self) -> bool:
        return self._state_lost

    def make_checkpoint(self, coord_table: ColumnTable,
                        coord_version: int) -> ShardCheckpoint:
        # Zero RPCs: the coordinator's clustered table is immutable, so a
        # reference to it at the checkpoint watermark IS a consistent
        # snapshot the shard can be deterministically rebuilt from.
        return ShardCheckpoint(kind="coord", table=coord_table.collapse(),
                               version=coord_version)

    def restore_checkpoint(self, ckpt: ShardCheckpoint,
                dims: Mapping[str, ColumnTable], plan: ShardPlan,
                ranges: RangeSet) -> None:
        self._build(plan, ranges, ckpt.table, dims, ckpt.version)

    def rebuild(self, plan: ShardPlan, ranges: RangeSet,
                clustered: ColumnTable, dims: Mapping[str, ColumnTable],
                device, inbox_cap: Optional[int], version: int) -> None:
        self._inbox_cap = inbox_cap
        self._build(plan, ranges, clustered, dims, version)

    # -- peer-replicated checkpoints -------------------------------------------
    def peer_put(self, sid: int, local: ColumnTable, plan_token: int) -> None:
        """Seed this server with a mirror of peer shard ``sid``'s local
        table (full ship — only at seed/re-seed; deltas keep it current)."""
        self._request("ckpt_put", (sid, local.collapse(), plan_token),
                      deadline_s=self._build_deadline_s)

    def peer_ship(self, sid: int, version: int, kind: str, payload) -> bool:
        """Apply one delta to the mirror of shard ``sid``; False when the
        mirror is missing or the delta would leave a version gap (the
        server drops the mirror — a gapped mirror is useless)."""
        return bool(self._request("ckpt_ship", (sid, version, kind, payload)))

    def peer_fetch(self, sid: int,
                   plan_token: int) -> Optional[Tuple[ColumnTable, int]]:
        """Fetch the mirror of shard ``sid``; None when absent or seeded
        under a different placement plan."""
        return self._request("ckpt_get", (sid, plan_token),
                             deadline_s=self._build_deadline_s)

    def build_local(self, plan: ShardPlan, ranges: RangeSet,
                    local: ColumnTable, dims: Mapping[str, ColumnTable],
                    inbox_cap: Optional[int]) -> None:
        """Rebuild this shard from an already-local table (peer-mirror
        recovery): no coordinator-table gather, no full-table reship."""
        self._inbox_cap = inbox_cap
        self._request(
            "build_local",
            (self.shard_id, plan.owner, plan.n_shards, ranges,
             local.collapse(), {k: v.collapse() for k, v in dims.items()},
             inbox_cap, self.shard_id),
            deadline_s=self._build_deadline_s)
        self._state_lost = False
        self._bits = self._bits if self._bits is not None else {}
        self._fault = None

    def clone_for_takeover(self) -> "SubprocessShardClient":
        """A fresh client over the SAME live server socket for a takeover
        coordinator.  No shard state moves — the new client re-learns the
        server's state cheaply via one ctl round trip (whose meta piggyback
        carries version, maintainer keys and dimension tokens); sketch-bit
        caches refill on the first catch_up."""
        c = object.__new__(SubprocessShardClient)
        c.shard_id = self.shard_id
        c._pool = self._pool
        c._inbox_cap = self._inbox_cap
        c._deadline_s = self._deadline_s
        c._build_deadline_s = self._build_deadline_s
        c._proc = self._proc
        c._fault = self._fault if self._fault in ("dead",) else None
        c._state_lost = True
        c._version = -1
        c._lag = 0
        c._bp = 0
        c._token = None
        c._mkeys = set()
        c._bits = None
        c._dims = {}
        c._pending_unregister = set()
        c.epoch = 0  # the owning engine stamps the real epoch after attach
        if c._proc is not None:
            try:
                token = c._request("state_token", (), ctl=True)
                c._state_lost = token is None
            except ShardUnavailableError:
                c._state_lost = True
        return c

    def close_client(self) -> None:
        """Release the server back to the warm pool (or reap it)."""
        proc, self._proc = self._proc, None
        if proc is not None:
            if self._fault in ("dead",) or not proc.alive:
                self._pool.discard(proc)
            else:
                self._pool.release(proc)
        self._fault = "dead"
        self._state_lost = True

    @property
    def pid(self) -> Optional[int]:
        """The server process id (None after a kill) — test/debug hook."""
        return self._proc.proc.pid if self._proc is not None else None


# ---------------------------------------------------------------------------
# Server side
# ---------------------------------------------------------------------------


class ShardServer:
    """One shard's server loop state: the ``FragmentShard`` plus the
    server-side halves of fault injection (stall = sleep per data op,
    flaky = fail the next N data ops)."""

    #: ops exempt from stall/flaky (fault control, lifecycle, and
    #: unregister — whose loopback counterpart has no fault guard either).
    CTL_OPS = ("ping", "set_stall", "set_flaky", "clear_faults", "reset",
               "shutdown", "unregister_many", "state_token")

    def __init__(self):
        self.shard: Optional[FragmentShard] = None
        self.stall_s = 0.0
        self.flaky_fails = 0
        self.closed = False
        # Highest coordinator epoch seen on a non-ctl op.  Process identity,
        # not shard state: survives shard rebuilds, zeroed only by ``reset``
        # (pool re-tenancy — a different coordinator's epoch space).
        self.epoch = 0
        # Peer-replicated checkpoints: sid -> (mirror table, plan token).
        # Delta-maintained by ``ckpt_ship``; recovery pulls shard-local
        # state from here instead of re-shipping the coordinator's table.
        self.peer_ckpts: Dict[int, Tuple[ColumnTable, int]] = {}

    # -- dispatch --------------------------------------------------------------
    def handle(self, msg: dict) -> dict:
        op = msg.get("op", "")
        args = msg.get("args", ())
        try:
            if op not in self.CTL_OPS:
                if self.stall_s > 0:
                    time.sleep(self.stall_s)
                if self.flaky_fails > 0:
                    self.flaky_fails -= 1
                    raise ShardUnavailableError(
                        f"shard dropped {op} (flaky)")
                # Epoch fence, AFTER the fault simulation (a stalled zombie
                # op must still be rejected, not served): monotone max, so a
                # newer coordinator's first op fences every older one out.
                epoch = int(msg.get("epoch", 0))
                if epoch < self.epoch:
                    raise StaleEpochError(
                        f"coordinator epoch {epoch} is fenced behind "
                        f"{self.epoch} ({op})")
                self.epoch = epoch
            value = self._dispatch(op, args)
            return {"ok": True, "value": value, "meta": self._meta(op)}
        except Exception as e:  # marshalled; the client re-raises by type
            return {"ok": False, "etype": type(e).__name__, "msg": str(e),
                    "meta": self._meta(op)}

    def _meta(self, op: str) -> dict:
        s = self.shard
        if s is None or s.table is None:
            return {"version": -1, "lag": 0, "bp": 0, "token": None,
                    "mkeys": (), "dims": {}, "bits": {}}
        meta = {
            "version": s.version,
            "lag": s.lag,
            "bp": s.backpressure_hits,
            "token": (s.table.uid, s.table.version),
            "mkeys": tuple(s.maintainers.keys()),
            "dims": {k: (v.uid, v.version) for k, v in s.dims.items()},
        }
        if op in ("build", "catch_up", "register", "update_dim"):
            # The only ops after which maintained bits can differ from the
            # client's cache — piggyback the fresh bits so the warm read
            # path never pays a separate bits_for round trip.
            meta["bits"] = {key: np.asarray(m.bits())
                            for key, m in s.maintainers.items()}
        return meta

    def _require_shard(self) -> FragmentShard:
        if self.shard is None:
            raise ShardUnavailableError("server has no shard state (build first)")
        return self.shard

    def _dispatch(self, op: str, args: tuple):
        if op == "ping":
            return "pong"
        if op == "set_stall":
            self.stall_s = float(args[0])
            return None
        if op == "set_flaky":
            self.flaky_fails = int(args[0])
            return None
        if op == "clear_faults":
            self.stall_s = 0.0
            self.flaky_fails = 0
            return None
        if op == "reset":
            self.shard = None
            self.stall_s = 0.0
            self.flaky_fails = 0
            self.epoch = 0
            self.peer_ckpts = {}
            return None
        if op == "shutdown":
            self.closed = True
            return None
        if op == "build":
            (shard_id, owner, n_shards, ranges, clustered, dims,
             inbox_cap, version, device_ord) = args
            plan = ShardPlan(n_shards=n_shards, owner=np.asarray(owner))
            self.shard = FragmentShard(
                shard_id, plan, ranges, clustered, dims,
                _pick_device(device_ord), inbox_cap=inbox_cap,
                version=version)
            return None
        if op == "unregister_many":
            if self.shard is not None:
                for key in args[0]:
                    self.shard.unregister(key)
            return None
        if op == "state_token":
            s = self.shard
            return (None if s is None or s.table is None
                    else (s.table.uid, s.table.version))
        if op == "ckpt_put":
            sid, table, token = args
            self.peer_ckpts[int(sid)] = (table, token)
            return None
        if op == "ckpt_ship":
            sid, version, kind, payload = args
            ent = self.peer_ckpts.get(int(sid))
            if ent is None:
                return False
            table, token = ent
            if version <= table.version:
                return True  # duplicate re-ship: idempotent skip
            if version > table.version + 1:
                # Version gap (an earlier delta never landed): a gapped
                # mirror can never be made current again — drop it so the
                # coordinator re-seeds instead of recovering stale state.
                self.peer_ckpts.pop(int(sid), None)
                return False
            table = (table.append(payload) if kind == "append"
                     else table.delete(payload))
            self.peer_ckpts[int(sid)] = (table, token)
            return True
        if op == "ckpt_get":
            sid, token = args
            ent = self.peer_ckpts.get(int(sid))
            if ent is None or ent[1] != token:
                # Absent, or seeded under a different placement plan: a
                # mirror gathered under the old owner map must never be
                # adopted after a rebalance.
                return None
            return (ent[0].collapse(), ent[0].version)
        if op == "build_local":
            (shard_id, owner, n_shards, ranges, local, dims,
             inbox_cap, device_ord) = args
            plan = ShardPlan(n_shards=n_shards, owner=np.asarray(owner))
            self.shard = FragmentShard.from_local(
                shard_id, plan, ranges, local, dims,
                device=_pick_device(device_ord), inbox_cap=inbox_cap)
            return None
        shard = self._require_shard()
        if op == "ship":
            version, kind, payload = args
            shard.ship(version, kind, payload)
            return None
        if op == "catch_up":
            return shard.catch_up(int(args[0]))
        if op == "register":
            key, q, ranges = args
            shard.register(key, q, ranges)
            return None
        if op == "bits_for":
            return shard.bits_for(args[0])
        if op == "partial":
            q, key, ranges, bits = args
            gv, sums, counts = shard.partial(q, key, ranges, bits)
            return ({k: np.asarray(v) for k, v in gv.items()},
                    np.asarray(sums), np.asarray(counts))
        if op == "block_arrays":
            key, ranges, bits, q = args
            inst = shard._instance(key, ranges, bits)
            if q.join is not None:
                flat, _ = shard.catalog.join(
                    inst, shard.dims[q.join.right], q.join.left_key,
                    q.join.right_key)
            else:
                flat = inst
            enc, where, vals = inner_block_arrays(q, flat, shard.catalog)
            return (int(enc.n_groups),
                    {k: np.asarray(v) for k, v in enc.group_values.items()},
                    np.asarray(enc.gid), np.asarray(where),
                    np.asarray(vals))
        if op == "update_dim":
            shard.update_dim(args[0])
            return None
        raise ValueError(f"unknown rpc op {op!r}")


def _pick_device(device_ord: Optional[int]):
    """The server's own device for its shard's columns (devices are not
    serializable across processes, so the coordinator sends an ordinal and
    the child resolves it against its *own* jax runtime — under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` each shard
    process pins a distinct emulated host device)."""
    import jax

    devices = jax.local_devices()
    if device_ord is None or len(devices) <= 1:
        return None
    return devices[device_ord % len(devices)]


def _stdin_watchdog() -> None:
    """Exit the moment the parent dies: the coordinator holds our stdin
    pipe, so EOF means the parent is gone and we are an orphan."""
    try:
        while True:
            chunk = sys.stdin.buffer.read(4096)
            if not chunk:
                break
    except Exception:
        pass
    os._exit(2)


def serve(path: str) -> None:
    """The subprocess entry: bind, accept one connection at a time, serve
    request/response until shutdown.  A broken connection (client timed
    out mid-stall and reconnected, coordinator dropped a partition) just
    re-enters accept — shard state survives across connections."""
    threading.Thread(target=_stdin_watchdog, daemon=True).start()
    # A respawned server (post-SIGKILL recovery, pool top-up) loads its
    # kernels from the persistent cache instead of compiling them again.
    enable_compile_cache()

    # Disjoint uid space: tables created in this process (local shard
    # tables, instances) must never collide with coordinator-created uids
    # arriving over the wire, or per-uid catalog caches would alias.
    from repro.core import table as table_mod
    table_mod._TABLE_UIDS = itertools.count(((os.getpid() & 0xFFFFF) << 40) | 1)

    srv = ShardServer()
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    sock.bind(path)
    sock.listen(4)
    while not srv.closed:
        conn, _ = sock.accept()
        try:
            while not srv.closed:
                seq, msg = transport.recv_msg(conn, deadline_s=None)
                resp = srv.handle(msg)
                transport.send_msg(conn, resp, seq)
        except (transport.RpcClosed, transport.FrameError, OSError):
            pass  # connection over; accept the next one
        finally:
            try:
                conn.close()
            except OSError:
                pass
    os._exit(0)


def main(argv: Optional[List[str]] = None) -> None:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print("usage: python -m repro.core.shard_rpc <socket-path>",
              file=sys.stderr)
        raise SystemExit(2)
    serve(args[0])


if __name__ == "__main__":
    main()
