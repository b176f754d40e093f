"""Host spans: named, nested intervals of the program's host work.

``span(name, **counts)`` is a context manager for one interval of host
work.  It does two things:

* it opens a ``jax.profiler.TraceAnnotation(name, **counts)``, so a
  profiler capture shows the span on the host plane beside the device ops,
  on the profiler's own clock;
* when it closes, it appends a :class:`Span` to a process-wide ring of the
  last :data:`RING_SPANS` finished spans, timed on ``time.perf_counter_ns``.

The ring is always on; :func:`snapshot` reads it.  A span's parent is the
innermost span open on the same thread when it opened.  Counts known only
when the work is done are set on the open span's handle::

    with spans.span("stream.drain", call=7) as sp:
        ...
        sp.set(records=n)

A span must not stay open across a generator's ``yield``: the caller's
time would land inside it, and the thread's span stack would hold a span
the generator may never close.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

#: How many finished spans the ring keeps (the oldest drop out first).
RING_SPANS = 16384


class Span(NamedTuple):
    """One finished span."""

    name: str
    id: int
    parent_id: Optional[int]   # None for a span opened with none open
    start_ns: int              # time.perf_counter_ns()
    end_ns: int
    counts: Dict[str, int]


_RING: "collections.deque[Span]" = collections.deque(maxlen=RING_SPANS)
_IDS = itertools.count(1)
_LOCAL = threading.local()


def _stack() -> List["_OpenSpan"]:
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    return stack


class _OpenSpan:
    """The handle ``span`` returns; ``set`` adds or overwrites counts."""

    __slots__ = ("name", "id", "parent_id", "counts", "start_ns", "_annotation")

    def __init__(self, name: str, counts: Dict[str, int]):
        self.name = name
        self.counts = counts
        self.id = self.parent_id = self.start_ns = None
        self._annotation = None

    def set(self, **counts: int) -> None:
        self.counts.update(counts)

    def __enter__(self) -> "_OpenSpan":
        stack = _stack()
        self.id = next(_IDS)
        self.parent_id = stack[-1].id if stack else None
        stack.append(self)
        self._annotation = jax.profiler.TraceAnnotation(self.name, **self.counts)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        self._annotation = None
        _stack().pop()
        _RING.append(Span(self.name, self.id, self.parent_id, self.start_ns,
                          end_ns, dict(self.counts)))


def span(name: str, **counts: int) -> _OpenSpan:
    """A context manager timing one interval of host work (module docstring)."""
    return _OpenSpan(name, counts)


def snapshot() -> List[Span]:
    """The finished spans still in the ring, oldest first."""
    return list(_RING)
