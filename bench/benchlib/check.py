"""The comparison that decides ``correct``.

A *view* is one parsed partition as host arrays: the column symbol string
``css``, each field's offset and length into it, each typed column's
value, validity and empty flag, and the §4.3 record flags.  ``columns``
keeps those of the program's result on the device; ``host_view`` copies
them to the host once the window has closed.  ``reference_view`` builds
the same from the plain reference, which is how a control
(``bench/controls/<name>.py``) is put in the program's place.
``compare`` holds a view to the reference's records:

* ``mismatches`` counts every exact disagreement: records completed in the
  partition (§4.4 carry), field bytes under quotes (§3.1/§3.2), fields in
  the right column (§3.3), integer and date values, validity and empty
  flags (type conversion), and the record flags (§4.3).  Its limit is 0.
* ``float_rel_gap`` is the widest relative gap between a float32 value
  and Python's ``float`` of the same field rounded to float32.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from benchlib import oracle

#: float32's smallest normal: the floor of a relative gap's denominator.
_TINY = float(np.finfo(np.float32).tiny)


@dataclasses.dataclass
class Tally:
    mismatches: int = 0
    float_rel_gap: float = 0.0
    floats: int = 0
    fields: int = 0
    first: List[str] = dataclasses.field(default_factory=list)

    def miss(self, count: int, what: str) -> None:
        if count:
            self.mismatches += int(count)
            if len(self.first) < 8:
                self.first.append(f"{what}: {int(count)}")

    def add(self, other: "Tally") -> None:
        self.mismatches += other.mismatches
        self.float_rel_gap = max(self.float_rel_gap, other.float_rel_gap)
        self.floats += other.floats
        self.fields += other.fields
        self.first.extend(other.first[:max(0, 8 - len(self.first))])


def columns(result, schema: Sequence[Tuple[str, str]]) -> dict:
    """What the program delivered for one partition, as it lies on the
    device: the typed columns (values, validity, empty flags), the string
    bytes with each field's offset and length, and the record flags."""
    v = result.validation
    return dict(
        css=result.css, off=result.field_offset, len=result.field_length,
        n=v.n_records, record_ok=v.record_ok, no_invalid=v.no_invalid,
        ok=v.ok,
        values={name: (result.values[name].value, result.values[name].valid,
                       result.values[name].empty) for name, _ in schema})


def nbytes(cols: dict) -> int:
    import jax

    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(cols))


def host_view(cols: dict) -> dict:
    """A partition's delivered columns, copied to the host."""
    import jax

    host = jax.device_get(cols)
    host["n"] = int(host["n"])
    return host


def _converted(fields: List[Optional[bytes]], dtype: str):
    conv = oracle.CONVERT[dtype]
    value = np.zeros(len(fields), np.float64 if dtype == "float32" else np.int64)
    valid = np.zeros(len(fields), bool)
    for i, f in enumerate(fields):
        if f:
            try:
                value[i] = conv(f)
                valid[i] = True
            except ValueError:
                pass
    return value, valid


def reference_view(records: List[List[bytes]], schema,
                   float_round: Optional[Callable] = None) -> dict:
    """A view built by the reference from ``records``; ``float_round``,
    where given, rounds the float columns (as a control does)."""
    n, ncols = len(records), len(schema)
    pieces, off, ln = [], np.zeros((ncols, n), np.int64), np.zeros((ncols, n), np.int64)
    pos = 0
    values = {}
    for c, (name, dtype) in enumerate(schema):
        fields = [r[c] if c < len(r) else b"" for r in records]
        for i, f in enumerate(fields):
            off[c, i], ln[c, i] = pos, len(f)
            pieces.append(f)
            pos += len(f)
        empty = ln[c] == 0
        if dtype == "str":
            values[name] = (np.zeros(n), ~empty, empty)
        else:
            value, valid = _converted(fields, dtype)
            if dtype == "float32" and float_round is not None:
                value = float_round(value).astype(np.float64)
            values[name] = (value, valid, empty)
    return dict(css=np.frombuffer(b"".join(pieces), np.uint8), off=off, len=ln,
                n=n, record_ok=np.array([len(r) == ncols for r in records]),
                no_invalid=True, ok=True, values=values)


def compare(view: dict, records: List[List[bytes]], schema,
            final: bool) -> Tally:
    """Hold one partition's view to the reference's ``records``."""
    t = Tally()
    ncols = len(schema)
    n_ref, n = len(records), int(view["n"])
    t.miss(abs(n - n_ref), "records completed")
    m = min(n, n_ref, view["off"].shape[1])
    want_ok = np.array([len(r) == ncols for r in records[:m]], bool)
    t.miss(np.sum(np.asarray(view["record_ok"][:m], bool) != want_ok),
           "record flags")
    t.miss(int(not bool(view["no_invalid"])), "invalid state")
    if final:
        t.miss(int(not bool(view["ok"])), "final partition not ok")
    css = np.asarray(view["css"])
    for c, (name, dtype) in enumerate(schema):
        fields = [r[c] if c < len(r) else b"" for r in records[:m]]
        length = np.array([len(f) for f in fields], np.int64)
        value, valid, empty = (np.asarray(a)[:m] for a in view["values"][name])
        t.fields += m
        t.miss(np.sum(empty.astype(bool) != (length == 0)), f"{name} empty")
        if dtype == "str":
            got_len = np.asarray(view["len"][c][:m], np.int64)
            t.miss(np.sum(got_len != length), f"{name} length")
            offs = np.asarray(view["off"][c][:m], np.int64)
            bad = sum(1 for i, f in enumerate(fields)
                      if got_len[i] == length[i]
                      and css[offs[i]:offs[i] + length[i]].tobytes() != f)
            t.miss(bad, f"{name} bytes")
            continue
        want, want_valid = _converted(fields, dtype)
        valid = valid.astype(bool)
        t.miss(np.sum(valid != want_valid), f"{name} validity")
        both = valid & want_valid
        if dtype == "float32":
            got = value.astype(np.float64)[both]
            gap = np.abs(got - want[both]) / np.maximum(np.abs(want[both]), _TINY)
            t.floats += int(both.sum())
            if gap.size:
                t.float_rel_gap = max(t.float_rel_gap, float(gap.max()))
            t.miss(np.sum(~np.isfinite(gap)), f"{name} not finite")
        else:
            t.miss(np.sum(value.astype(np.int64)[both] != want[both]),
                   f"{name} values")
    return t
