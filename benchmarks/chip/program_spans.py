"""Helpers of the readers of the program's own spans and counters
(``RunInfo.trace``: a record with ``spans``, each ``name``, ``start_ns``,
``end_ns`` and ``parent``, with ``shared`` spans and counters).  A run of a
program without such records reads as nothing: ``records`` is then empty and
each reader returns ``None``."""


def records(infos) -> list:
    """The record of each ``RunInfo`` given; empty when any lacks one."""
    out = [getattr(i, "trace", None) for i in infos if i is not None]
    return out if out and all(r is not None for r in out) else []


def window_records(run) -> list:
    return records(run.window_infos)


def mean(values) -> float:
    values = list(values)
    return sum(values) / len(values)


def dur_s(span) -> float:
    return (span.end_ns - span.start_ns) / 1e9


def seconds(spans, name: str) -> float:
    return sum(dur_s(s) for s in spans if s.name == name)


def within(span, other) -> bool:
    while span is not None:
        if span is other:
            return True
        span = span.parent
    return False


def ancestor(span, name: str):
    """The nearest span named ``name`` at or above ``span``, or ``None``."""
    while span is not None and span.name != name:
        span = span.parent
    return span
