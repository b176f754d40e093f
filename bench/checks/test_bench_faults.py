"""A run with the timed path broken underneath comes out not correct: for
each fault a cell can have, the rest of the run is driven as usual (only
the look for a chip is skipped).  Faults: a step that returns its state
(the §4.4 carry) unchanged; an answer altered where it is produced.  (One
stream on one chip: no batch to leave half of, no exchange between chips
to leave out.)"""
import pytest

from cpu_cells import run_tiny, tiny_root
from repro.core.streaming import StreamSession


def _wrap_step(monkeypatch, change):
    build = StreamSession._build_step

    def broken(self):
        step = build(self)
        return lambda buf, ln, *rest: change(step, buf, ln, *rest)

    monkeypatch.setattr(StreamSession, "_build_step", broken)


def stale_carry(step, buf, ln, *rest):
    result, _buf, _ln, aux = step(buf, ln, *rest)
    return result, buf, ln, aux


def altered_value(step, buf, ln, *rest):
    result, buf, ln, aux = step(buf, ln, *rest)
    p = result.values["stars"]
    values = dict(result.values, stars=p._replace(value=p.value.at[..., 0].add(1)))
    return result._replace(values=values), buf, ln, aux


@pytest.mark.parametrize("change", [stale_carry, altered_value])
def test_broken_step_is_not_correct(tmp_path, monkeypatch, change):
    _wrap_step(monkeypatch, change)
    res = run_tiny(tiny_root(tmp_path), "tiny-stream")
    assert not res["correct"]
    assert res["checks"]["mismatches"]["value"] > 0

