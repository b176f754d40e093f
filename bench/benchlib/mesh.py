"""Readers of a trace taken on several chips at once.

``trace.Trace.busy_s`` and ``matching_s`` take one union over every
chip's ops and divide it by the chip count, which on chips running in
lockstep reads a quarter of each chip's busy time on four.  These take the
union alone: wall time in which an op ran on *any* chip.
"""
from __future__ import annotations

import re
from typing import Optional, Sequence

from benchlib import trace as trace_mod


def any_chip_s(tr, patterns: Optional[Sequence[str]] = None) -> float:
    """Seconds of the window in which an op (whose label matches one of
    ``patterns``, where given) ran on any chip."""
    rx = None if patterns is None else [re.compile(p) for p in patterns]
    return trace_mod.union_ns(
        (op.start, op.end) for op in tr.clipped()
        if rx is None or any(r.search(op.stack) for r in rx)) / 1e9


def ms_per_gb(r, patterns: Sequence[str]) -> Optional[float]:
    """Wall ms per GB of source bytes in which an op matching ``patterns``
    ran on any chip."""
    if r.trace is None or not r.source_bytes:
        return None
    s = any_chip_s(r.trace, patterns)
    return 1e3 * s / (r.source_bytes / 1e9) if s > 0 else None


def span_s(tr, names: Sequence[str]) -> float:
    """Host seconds of the window's spans called one of ``names`` (each
    clipped to the window)."""
    lo, hi = tr.window
    return sum(max(0, min(e, hi) - max(s, lo))
               for name, s, e in tr.spans if name in names) / 1e9
