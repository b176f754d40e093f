"""Device traces: capture with the JAX profiler, and reduce to intervals.

The reduction reads the profiler's ``.xplane.pb`` (``benchlib.xplane``):
every event on the ``XLA Ops`` line of a ``/device:TPU:<n>`` plane is a
device operation.  Each op is labelled by what JAX recorded for it: its
name stack (the ``tf_op`` stat, e.g. ``jit(step_one)/jit(partition_tags)/
scatter``) and the program module its source stack starts in (e.g.
``repro/core/partition.py``).  A metric claims the ops whose label matches
one of its patterns.  The benchmark's own host spans, written with
``jax.profiler.TraceAnnotation``, are read from the host plane on the same
clock.  ``Trace`` is what the per-layer metric readers see.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: The host spans of the bulk window, the default when a trace is read
#: without the names its ``Tracer`` wrote.
SPANS = ("window", "feeding source", "in parse_streams")



@dataclasses.dataclass
class Op:
    name: str
    stack: str
    start: int   # ns
    dur: int     # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: List[Op]                       # device ops, all devices
    spans: List[Tuple[str, int, int]]   # (name, start ns, end ns)
    window: Tuple[int, int]             # the traced window, ns
    devices: int = 1

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def clipped(self) -> List[Op]:
        lo, hi = self.window
        out = []
        for op in self.ops:
            s, e = max(op.start, lo), min(op.end, hi)
            if e > s:
                out.append(Op(op.name, op.stack, s, e - s))
        return out

    def busy_s(self) -> float:
        """Union of device-op intervals inside the window, averaged over
        the devices."""
        return union_ns((op.start, op.end) for op in self.clipped()) / 1e9 / self.devices

    def matching_s(self, patterns: Sequence[str]) -> float:
        """Device seconds in which an op whose label matches a pattern ran
        (a union: an op nested in another, such as a conditional's branch,
        counts once)."""
        rx = [re.compile(p) for p in patterns]
        return union_ns((op.start, op.end) for op in self.clipped()
                        if any(r.search(op.stack) for r in rx)) / 1e9 / self.devices

    def idle_gaps(self, k: int = 10) -> List[Tuple[str, float]]:
        """The ``k`` longest gaps between device ops inside the window,
        longest first, each named by the innermost benchmark span that
        covers its middle."""
        lo, hi = self.window
        gaps, at = [], lo
        for s, e in merge((op.start, op.end) for op in self.clipped()):
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        if hi > at:
            gaps.append((at, hi))
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
            mid = (s + e) // 2
            cover = [sp for sp in self.spans
                     if sp[0] != "window" and sp[1] <= mid < sp[2]]
            label = min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover else "no span"
            out.append((label, (e - s) / 1e9))
        return out

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """Device seconds by op, summed over the window, largest first."""
        tot: Dict[str, int] = {}
        for op in self.clipped():
            key = f"{layer_key(op.stack)}:{_op_kind(op.name)}"
            tot[key] = tot.get(key, 0) + op.dur
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [(name, ns / 1e9 / self.devices) for name, ns in top]


def layer_key(label: str) -> str:
    """A short name for an op's label: the innermost named jitted function
    of its name stack, and the program module it came from."""
    stack, _, module = label.partition(" @ ")
    names = re.findall(r"jit\(([^)]*)\)", stack)
    return f"{names[-1] if names else '?'}@{module.rsplit('/', 1)[-1] or '?'}"


def label(stats: Dict[str, object]) -> str:
    """``<name stack> @ <program module of the innermost source frame>``."""
    stack = str(stats.get("tf_op", "")).rstrip(":")
    module = ""
    for frame in str(stats.get("source_stack", "")).split("\n"):
        if "repro/" in frame:
            module = "repro/" + frame.split("repro/", 1)[1].split(":", 1)[0]
            break
    return f"{stack} @ {module}"


def _op_kind(name: str) -> str:
    """``%fusion.12 = ... fusion(...)`` → ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.]\d+$", "", head)


def merge(ivs: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def union_ns(ivs: Iterable[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in merge(ivs))


def read(path: str, span_names: Iterable[str] = SPANS) -> Trace:
    """A ``Trace`` from one ``.xplane.pb``, with the host spans called
    ``span_names``.  The window is the ``window`` span when the trace has
    one, else the device ops' extent."""
    span_names = set(span_names)
    from benchlib import xplane

    ops, spans, devices = [], [], set()
    for plane in xplane.planes(path):
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                devices.add(plane.name)
                for ev in line.events:
                    ops.append(Op(ev.name, label(ev.stats), int(ev.start_ns),
                                  int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in span_names:
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    win = [sp for sp in spans if sp[0] == "window"]
    if win:
        window = (win[0][1], win[0][2])
    elif ops:
        window = (min(o.start for o in ops), max(o.end for o in ops))
    else:
        window = (0, 0)
    return Trace(ops, spans, window, max(1, len(devices)))


class Tracer:
    """Host spans, and a device trace of the window when ``enabled``.

    Off, ``span`` is a no-op context, so an untraced run carries no
    annotations at all."""

    def __init__(self, enabled: bool):
        self.enabled = bool(enabled)
        self.dir: Optional[str] = None
        self.names = {"window"}

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        self.names.add(name)
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        if self.enabled:
            import jax
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host spans are the benchmark's
            opts.enable_hlo_proto = False   # the op labels are in the events
            jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> Optional[str]:
        """Stop tracing; returns the ``.xplane.pb`` path."""
        if not self.enabled:
            return None
        import jax
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        return found[0] if found else None

    def cleanup(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None
