"""A reader of the profiler's ``.xplane.pb`` (TSL's ``XSpace`` protobuf),
written against the message layout of ``tsl/profiler/protobuf/xplane.proto``
with a small wire-format decoder, so that the benchmark needs no protobuf
classes.  It keeps what ``jax.profiler.ProfileData`` leaves out: each
event's metadata stats, where a device op carries its name stack
(``tf_op``), its HLO category and its source stack.

Field numbers (xplane.proto): XSpace.planes 1; XPlane.name 2, lines 3,
event_metadata 4 (map entry: key 1, value 2), stat_metadata 5; XLine.name
2, timestamp_ns 3, events 4; XEvent.metadata_id 1, offset_ps 2,
duration_ps 3, stats 4; XEventMetadata.id 1, name 2, display_name 4,
stats 5; XStatMetadata.id 1, name 2; XStat.metadata_id 1, then one of
double 2, uint64 3, int64 4, str 5, bytes 6, ref 7.
"""
from __future__ import annotations

import dataclasses
import struct
from typing import Dict, Iterator, List, Tuple


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = s = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes) -> Iterator[Tuple[int, int, object]]:
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 1:
            v, i = b[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wt == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield f, wt, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(b: bytes, names: Dict[int, str], refs: Dict[int, str]):
    mid, val = 0, None
    for f, _wt, v in _fields(b):
        if f == 1:
            mid = v
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            val = _signed(v) if f == 4 else v
        elif f in (5, 6):
            val = v.decode(errors="replace") if f == 5 else v
        elif f == 7:
            val = refs.get(v, "")
    return names.get(mid, str(mid)), val


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    duration_ns: float
    stats: Dict[str, object]


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


def _plane(b: bytes) -> Plane:
    name, raw_lines, raw_md, stat_names = "", [], [], {}
    for f, _wt, v in _fields(b):
        if f == 2:
            name = v.decode(errors="replace")
        elif f == 3:
            raw_lines.append(v)
        elif f == 4:
            raw_md.append(v)
        elif f == 5:
            entry = {k: x for k, _w, x in _fields(v)}
            sm = {k: x for k, _w, x in _fields(entry.get(2, b""))}
            stat_names[sm.get(1, 0)] = sm.get(2, b"").decode(errors="replace")
    refs = stat_names  # a ref stat's value names a stat metadata entry
    meta: Dict[int, Tuple[str, Dict[str, object]]] = {}
    for v in raw_md:
        entry = {k: x for k, _w, x in _fields(v)}
        em_name, em_stats, em_id = "", {}, entry.get(1, 0)
        for f, _wt, x in _fields(entry.get(2, b"")):
            if f == 2:
                em_name = x.decode(errors="replace")
            elif f == 5:
                k, val = _stat(x, stat_names, refs)
                em_stats[k] = val
        meta[em_id] = (em_name, em_stats)
    lines = []
    for lb in raw_lines:
        lname, ts, events = "", 0, []
        raw_events = []
        for f, _wt, v in _fields(lb):
            if f == 2:
                lname = v.decode(errors="replace")
            elif f == 3:
                ts = _signed(v)
            elif f == 4:
                raw_events.append(v)
        for eb in raw_events:
            mid = off = dur = 0
            stats = {}
            for f, _wt, v in _fields(eb):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = _signed(v)
                elif f == 3:
                    dur = _signed(v)
                elif f == 4:
                    k, val = _stat(v, stat_names, refs)
                    stats[k] = val
            em_name, em_stats = meta.get(mid, ("", {}))
            events.append(Event(em_name, ts + off / 1e3, dur / 1e3,
                                dict(em_stats, **stats)))
        lines.append(Line(lname, events))
    return Plane(name, lines)


def planes(path: str) -> List[Plane]:
    with open(path, "rb") as f:
        data = f.read()
    return [_plane(v) for f, _wt, v in _fields(data) if f == 1]
