"""Host spans, counters and device scopes of the program.

``span(name)`` times a block of host code: it opens a
``jax.profiler.TraceAnnotation("repro." + name)``, which costs nothing
without a profiler and lands on the profiler's clock when one runs, and
appends ``(name, start, end)`` on ``time.perf_counter`` to a bounded ring,
so that the host's share of every call can be read back without a trace.
``record`` adds such an interval that is not a ``with`` block (a query's
wait in the queue), ``count`` adds to a named counter.

``scope(name)`` names device work: a ``jax.named_scope("repro." + name)``,
compile-time metadata only. Every operation traced inside it carries the
name in its ``op_name`` path, so a device trace or the compiled HLO can
put each operation under the innermost ``repro.`` scope that holds it.
It works as a context manager and as a decorator.

Nothing is switched on or off: a span costs a few microseconds of host
time, and the ring keeps the newest ``RING`` records.
"""
from __future__ import annotations

import collections
import time
from contextlib import contextmanager

import jax

__all__ = ["PREFIX", "RING", "span", "record", "count", "scope", "records",
           "counters", "reset"]

PREFIX = "repro."  # of every span's annotation and every scope's name
RING = 65536

_records: collections.deque = collections.deque(maxlen=RING)
_counters: dict[str, int] = {}


@contextmanager
def span(name: str):
    """Time the block as ``name`` (host clock, and the profiler's)."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield
    finally:
        _records.append((name, t0, time.perf_counter()))


def record(name: str, t0: float, t1: float) -> None:
    """Add an interval ``[t0, t1]`` on ``time.perf_counter`` as ``name``."""
    _records.append((name, t0, t1))


def count(name: str, k: int = 1) -> None:
    _counters[name] = _counters.get(name, 0) + int(k)


def scope(name: str):
    """``jax.named_scope`` for the device work traced inside it."""
    return jax.named_scope(PREFIX + name)


def records() -> list[tuple[str, float, float]]:
    """The ring's ``(name, start, end)`` records, oldest first."""
    return list(_records)


def counters() -> dict[str, int]:
    return dict(_counters)


def reset() -> None:
    _records.clear()
    _counters.clear()
