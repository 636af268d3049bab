"""The program's tracing: device scopes, host spans and counters.

- ``scope(name)`` names the device operations traced inside it
  (``jax.named_scope``): the name lands in each operation's metadata, where
  a profiler trace of the chip shows it. Compile-time only; nothing runs.
- ``span(name)`` times a host phase. It enters
  ``jax.profiler.TraceAnnotation(name)``, so that a running profiler shows
  it beside the device events, and keeps ``(name, start_ns, end_ns,
  parent)`` in memory, on the clock the profiler stamps host events with.
- ``gauge(name, value)`` sets a counter.

Spans and counters are coarse: a few per graph build or executor lowering,
never per task or per solve. The in-memory record is therefore always on;
it keeps the newest ``MAX_SPANS`` spans, so a long-lived process that
builds graph after graph holds a bounded record. ``snapshot()`` reads it
and ``reset()`` clears it.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, Iterator, NamedTuple, Optional

import jax

MAX_SPANS = 1024


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]


_lock = threading.Lock()
_spans: collections.deque = collections.deque(maxlen=MAX_SPANS)
_gauges: Dict[str, object] = {}
_open = threading.local()           # this thread's enclosing spans


def scope(name: str):
    """Name the device operations traced inside the block."""
    return jax.named_scope(name)


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Time the block as a host span named ``name``; its parent is the
    innermost span open on this thread when it starts."""
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    parent = stack[-1] if stack else None
    stack.append(name)
    try:
        with jax.profiler.TraceAnnotation(name):
            # the profiler stamps host events with the wall clock
            start = time.time_ns()
            yield
            end = time.time_ns()
    finally:
        stack.pop()
    with _lock:
        _spans.append(Span(name, start, end, parent))


def gauge(name: str, value) -> None:
    """Set the counter ``name`` (a number, or a mapping of numbers)."""
    with _lock:
        _gauges[name] = value


def snapshot() -> dict:
    """``{"spans": [Span, ...], "gauges": {name: value}}``, oldest span
    first."""
    with _lock:
        return {"spans": list(_spans), "gauges": dict(_gauges)}


def reset() -> None:
    with _lock:
        _spans.clear()
        _gauges.clear()
