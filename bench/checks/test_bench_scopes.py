"""The readers of the program's stage scopes and host spans, on a hand-made
trace and span list."""
import pytest

import cpu_cells  # noqa: F401  (puts bench/ on the path)
from benchlib import cells, scopes, trace

STAGES = ("contexts", "ids", "tag", "partition", "gather", "fields",
          "convert", "validate", "carry")
GB = 1e9


class _Readings:
    def __init__(self, t, source_bytes=GB):
        self.trace, self.source_bytes = t, source_bytes


def _op(s, e, stack):
    return trace.Op("%f.1 = f32[] fusion()", f"{stack} @ repro/core/x.py", s, e - s)


def _trace():
    """One op per stage, 10 ns apart and 10 + k ns long, each with an op
    nested inside it; and ops whose names only look like a stage's."""
    ops = []
    for k, st in enumerate(STAGES):
        s = 100 * k
        ops.append(_op(s, s + 10 + k, f"jit(step_one)/stage.{st}/jit(f)/op"))
        ops.append(_op(s + 1, s + 5, f"jit(step_one)/stage.{st}/pallas_call"))
    ops.append(_op(950, 960, "jit(step_one)/stage.gathered/gather"))
    ops.append(_op(970, 975, "jit(step_one)/xstage.ids/add"))
    return trace.Trace(ops, [("window", 0, 1000)], (0, 1000))


def test_stage_readers_split_the_scoped_time():
    cell = cells.load("yelp-bulk")
    t = _trace()
    got = {st: cell.reader(f"stage_ms_per_GB.{st}").read(_Readings(t))
           for st in STAGES}
    for k, st in enumerate(STAGES):
        assert got[st] == pytest.approx(1e3 * (10 + k) * 1e-9)
    unscoped_ns = 15
    assert sum(got.values()) == pytest.approx(
        1e3 * (t.busy_s() - unscoped_ns * 1e-9))


def test_stage_readers_find_nothing_without_scopes():
    cell = cells.load("taxi-bulk")
    t = trace.Trace([_op(0, 10, "jit(step_one)/gather")], [], (0, 10))
    for st in STAGES:
        assert cell.reader(f"stage_ms_per_GB.{st}").read(_Readings(t)) is None


def _sp(name, id_, parent, s, e, call, **counts):
    from repro.core.spans import Span
    return Span(name, id_, parent, s, e, dict(counts, call=call))


def test_host_reader_takes_the_newest_call_less_the_children(monkeypatch):
    from repro.core import spans
    listed = [
        _sp("stream.stage", 1, None, 0, 1000, call=3),       # an older call
        _sp("stream.stage", 10, None, 0, 100, call=4, bytes=8),
        _sp("stream.pull", 11, 10, 10, 40, call=4),
        _sp("stream.pull", 12, 10, 50, 60, call=4),
        _sp("stream.dispatch", 13, None, 100, 120, call=4),
        _sp("stream.drain", 14, None, 120, 200, call=4, records=2),
        _sp("stream.wait", 15, 14, 130, 190, call=4),
        _sp("other", 16, None, 0, 5000, call=4),
    ]
    assert scopes.engine_host_ns(listed) == (100 - 30 - 10) + 20 + (80 - 60)
    monkeypatch.setattr(spans, "snapshot", lambda: listed)
    reader = cells.load("yelp-bulk").reader("stream_host_ms_per_GB")
    assert reader.read(_Readings(None, source_bytes=2 * GB)) == pytest.approx(
        1e-6 * 100 / 2)
    monkeypatch.setattr(spans, "snapshot", lambda: [])
    assert reader.read(_Readings(None)) is None
