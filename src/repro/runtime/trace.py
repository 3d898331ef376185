"""Spans and counters of one query, made where the work happens.

A :class:`Record` holds what one query, or one ``run_batch`` call, did: its
spans, each ``(name, start, end, parent)``, and its counters (host syncs,
bytes copied device to host, catalog misses and evictions, and the names of
the programs compiled while it was current, and the rows and bytes of the
join layouts it materialized).  ``RunInfo.trace`` carries a query's record,
so it lives and dies with the ``RunInfo``; a query's record made inside a
batch points to the batch's record (``Record.outer``).

``span(name)`` appends to the record that is current in this context, and
also enters ``jax.profiler.TraceAnnotation(name)`` with the batch number and
the query's position in the batch as arguments, so a profiler session shows
the same span beside the device ops.  Timestamps come from ``time.time_ns()``:
the profiler stamps host events with the same clock (``CLOCK_REALTIME``), so
a span of a record and the device ops of a trace can be joined by time once
the trace's ``profile_start_time`` is added back to its relative starts.

``to_host(x)`` is ``np.asarray(x)`` under a ``repro.to_host`` span.  It
counts one sync and ``x.nbytes`` copied into the current record.  The
host's wait for the device shows up as that span, so a dispatch span such as
``repro.aggregate`` covers only the enqueue.

With no record current, ``span`` still measures and annotates but keeps
nothing.  Work shared by several queries (a miss wave's selection, scan and
bitmap) is recorded once on the batch's record; each member keeps a
reference to it in ``Record.shared`` together with the number of members
that share it.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import itertools
import threading
import time
from typing import Iterator, List, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(eq=False)
class Span:
    name: str
    start_ns: int
    end_ns: int = 0
    parent: Optional["Span"] = None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


@dataclasses.dataclass(eq=False)
class Record:
    """Spans and counters of one query (``pos`` set) or one batch."""

    batch: int
    pos: Optional[int] = None
    spans: List[Span] = dataclasses.field(default_factory=list)
    # Spans on the batch's record that this query's work ran in, each with
    # the number of queries that share it.
    shared: List[Tuple[Span, int]] = dataclasses.field(default_factory=list)
    host_syncs: int = 0
    d2h_bytes: int = 0
    catalog_misses: int = 0
    catalog_evictions: int = 0
    compiled: List[str] = dataclasses.field(default_factory=list)
    # Join layouts materialized (``Catalog.join``): rows, device bytes, and
    # how many were sketch instances gathered from a cached layout.
    join_rows: int = 0
    join_bytes: int = 0
    join_derived: int = 0
    # The batch's record, for a query's record made inside a batch: what the
    # batch's shared spans counted lives there.
    outer: Optional["Record"] = None

    def last(self, name: str) -> Optional[Span]:
        for s in reversed(self.spans):
            if s.name == name:
                return s
        return None

    def share(self, span: Span, n: int) -> float:
        """Point this query at a shared span; returns its share in seconds."""
        self.shared.append((span, n))
        return span.seconds / n


_STATE: contextvars.ContextVar[Tuple[Optional[Record], Optional[Span]]] = \
    contextvars.ContextVar("repro_trace", default=(None, None))
_BATCHES = itertools.count()


class span:
    """``with span(name) as s:`` times the block into ``s`` (a :class:`Span`)
    and appends it to the current record, under the innermost open span."""

    __slots__ = ("name", "_span", "_token", "_ann")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> Span:
        rec, parent = _STATE.get()
        s = Span(self.name, 0, 0, parent)
        if rec is None:
            self._token = None
            self._ann = TraceAnnotation(self.name)
        else:
            rec.spans.append(s)
            self._token = _STATE.set((rec, s))
            self._ann = (TraceAnnotation(self.name, batch=rec.batch) if rec.pos is None
                         else TraceAnnotation(self.name, batch=rec.batch, pos=rec.pos))
        self._span = s
        s.start_ns = time.time_ns()
        self._ann.__enter__()
        return s

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        self._span.end_ns = time.time_ns()
        if self._token is not None:
            _STATE.reset(self._token)


def to_host(x) -> np.ndarray:
    """``np.asarray(x)``, counted as one sync and ``nbytes`` copied when
    ``x`` is a device array."""
    with span("repro.to_host"):
        out = np.asarray(x)
    rec = _STATE.get()[0]
    if rec is not None and not isinstance(x, np.ndarray):
        rec.host_syncs += 1
        rec.d2h_bytes += out.nbytes
    return out


def count(**counters: int) -> None:
    """Add to the named counters of the current record, if one is current."""
    rec = _STATE.get()[0]
    if rec is not None:
        for name, n in counters.items():
            setattr(rec, name, getattr(rec, name) + n)


def _on_event(name: str, secs: float, **kw) -> None:
    if name == COMPILE_EVENT:
        rec = _STATE.get()[0]
        if rec is not None:
            rec.compiled.append(str(kw.get("fun_name", "?")))


def _listen() -> None:
    """Register the compile listener, once per process; with no record
    current it does nothing."""
    global _listening
    with _listen_lock:
        if not _listening:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(_on_event)
            _listening = True


_listening = False
_listen_lock = threading.Lock()


@contextlib.contextmanager
def recording(rec: Record) -> Iterator[Record]:
    """Make ``rec`` the current record; spans opened inside it nest under
    the span open outside."""
    if not _listening:
        _listen()
    token = _STATE.set((rec, _STATE.get()[1]))
    try:
        yield rec
    finally:
        _STATE.reset(token)


def query_record(pos: int) -> Record:
    """A fresh record for the query at ``pos`` of the current batch (a batch
    of its own when none is current)."""
    rec = _STATE.get()[0]
    if rec is None:
        return Record(next(_BATCHES), pos)
    return Record(rec.batch, pos, outer=rec if rec.pos is None else rec.outer)


@contextlib.contextmanager
def batch() -> Iterator[Record]:
    """A numbered batch record, current and under a ``repro.run_batch`` span."""
    rec = Record(next(_BATCHES))
    with recording(rec), span("repro.run_batch"):
        yield rec
