"""Readers of what the program records about itself.

Device side: each stage of the parse step runs under one ``stage.<name>``
scope (``core/stages.py``, ``core/streaming.py``), so the name stack of
every device op of the step holds exactly one such path component.  A
stage metric claims the ops whose name stack holds its component.

Host side: ``repro.core.spans`` keeps the stream engine's spans
(``stream.stage``, ``stream.pull``, ``stream.dispatch``, ``stream.drain``,
``stream.wait``) in memory, each with the ``call`` id of its
``parse_streams`` call.

A program without the scopes or the spans gives these readers nothing to
read: they return ``None``.
"""
from __future__ import annotations

from typing import Iterable, Optional, Tuple

#: The engine's host work: its top-level spans, each less its children
#: (the caller's source iterator, the wait for the device).
ENGINE_SPANS = ("stream.stage", "stream.dispatch", "stream.drain")


def patterns(stage: str) -> Tuple[str, ...]:
    """Name patterns of the ops under ``stage.<stage>``, matched as a whole
    path component of the name stack (``trace.label``'s
    ``<name stack> @ <module>``)."""
    return (r"(^|/)stage\.%s(/| @)" % stage,)


def ms_per_gb(r, pats) -> Optional[float]:
    """Device ms per GB of source bytes in which an op matching ``pats`` ran."""
    if r.trace is None or not r.source_bytes:
        return None
    s = r.trace.matching_s(pats)
    return 1e3 * s / (r.source_bytes / 1e9) if s > 0 else None


def engine_host_ns(spans: Iterable) -> Optional[int]:
    """Host ns of the stream engine's own work in its newest ``parse_streams``
    call: the ``ENGINE_SPANS`` of that call, each less the time its child
    spans cover."""
    spans = [sp for sp in spans if sp.name.startswith("stream.")
             and "call" in sp.counts]
    if not spans:
        return None
    call = max(sp.counts["call"] for sp in spans)
    mine = [sp for sp in spans if sp.counts["call"] == call]
    top = {sp.id for sp in mine if sp.name in ENGINE_SPANS}
    total = 0
    for sp in mine:
        dur = sp.end_ns - sp.start_ns
        if sp.id in top:
            total += dur
        elif sp.parent_id in top:
            total -= dur
    return total


def host_ms_per_gb(r) -> Optional[float]:
    """``engine_host_ns`` of the program's span ring, in ms per GB of
    source bytes."""
    if not r.source_bytes:
        return None
    try:
        from repro.core import spans
    except ImportError:     # a program that records no host spans
        return None
    ns = engine_host_ns(spans.snapshot())
    return None if ns is None else 1e-6 * ns / (r.source_bytes / 1e9)
