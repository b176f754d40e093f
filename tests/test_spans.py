"""The program's own tracing: one ``stage.*`` scope per stage of the compiled
parse step, and the stream engine's host spans (``repro.core.spans``)."""
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core import Parser, ParserConfig, Schema, make_csv_dfa, spans
from repro.core.streaming import StreamSession

DATA = b"".join(b'%d,"w,%d\nx",%d.5\n' % (i, i * 7, i) for i in range(30))


def _session(backend: str) -> StreamSession:
    cfg = ParserConfig(dfa=make_csv_dfa(),
                       schema=Schema.of(("a", "int32"), ("b", "str"),
                                        ("c", "float32")),
                       max_records=16, chunk_size=16, backend=backend,
                       validate_columns=True)
    return StreamSession(Parser(cfg), 48, max_carry_bytes=64)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_every_op_of_the_step_is_under_one_stage_scope(backend):
    sess = _session(backend)
    args = (jax.ShapeDtypeStruct((sess.capacity,), jnp.uint8),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((48,), jnp.uint8),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.bool_))
    text = sess._step.lower(*args).compile().as_text()
    names = [n for n in re.findall(r'op_name="([^"]*)"', text)
             if n.startswith("jit(step_one)/")]
    assert names
    unscoped = [n for n in names
                if sum(p.startswith("stage.") for p in n.split("/")) != 1]
    assert unscoped == []
    seen = {p for n in names for p in n.split("/") if p.startswith("stage.")}
    assert seen == {"stage." + s for s in (
        "carry", "contexts", "ids", "tag", "partition", "gather", "fields",
        "convert", "validate")}


def _overlaps(sp, lo, hi):
    return sp.start_ns < hi and sp.end_ns > lo


@pytest.fixture(scope="module")
def sess():
    return _session("reference")


def test_engine_spans_count_what_the_stats_count(sess):
    gen = sess.parse_streams([[DATA[i:i + 40] for i in range(0, len(DATA), 40)]])
    caller = []                       # the caller's time between next() calls
    while next(gen, None) is not None:
        t1 = time.perf_counter_ns()
        time.sleep(0.001)
        caller.append((t1, time.perf_counter_ns()))
    assert spans._stack() == []
    stats = sess.call_stats[0]
    assert stats.bytes_reparsed > 0 and stats.partitions > 2

    call = max(sp.counts["call"] for sp in spans.snapshot()
               if sp.name.startswith("stream."))
    mine = [sp for sp in spans.snapshot() if sp.counts.get("call") == call]
    by = {}
    for sp in mine:
        by.setdefault(sp.name, []).append(sp)
    rounds = len(by["stream.dispatch"])
    assert rounds == stats.partitions
    assert len(by["stream.stage"]) == rounds + 1      # the last finds nothing
    assert len(by["stream.drain"]) == len(by["stream.wait"]) == rounds
    ids = {name: {sp.id for sp in group} for name, group in by.items()}
    assert all(sp.parent_id in ids["stream.stage"] for sp in by["stream.pull"])
    assert all(sp.parent_id in ids["stream.drain"] for sp in by["stream.wait"])
    assert all(sp.parent_id is None for name in
               ("stream.stage", "stream.dispatch", "stream.drain")
               for sp in by[name])
    assert sum(sp.counts["bytes"] for sp in by["stream.stage"]) == stats.bytes_in
    assert sum(sp.counts["records"] for sp in by["stream.drain"]) == stats.records
    assert sum(sp.counts["carry_bytes"] for sp in by["stream.drain"]) \
        == stats.bytes_reparsed
    assert not [sp for sp in mine for lo, hi in caller if _overlaps(sp, lo, hi)]


def test_no_span_is_left_open_by_an_abandoned_call(sess):
    gen = sess.parse_streams([[DATA]])
    next(gen)
    assert spans._stack() == []
    gen.close()
    sess.reset()


def test_span_ring_is_bounded_and_keeps_the_newest():
    for i in range(spans.RING_SPANS + 10):
        with spans.span("t.ring", i=i) as sp:
            sp.set(j=i)
    got = spans.snapshot()
    assert len(got) == spans.RING_SPANS
    assert got[-1].name == "t.ring"
    assert got[-1].counts == {"i": spans.RING_SPANS + 9, "j": spans.RING_SPANS + 9}
    assert got[0].counts["i"] == 10
