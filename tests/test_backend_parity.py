"""Backend parity: ``backend="pallas"`` must be bit-identical to
``backend="reference"`` for every driver (the acceptance bar for the
pluggable stage-backend layer in core/backends.py / core/stages.py).

Every comparison is exact (``np.array_equal``, no tolerance): both backends
run the same integer/byte pipelines, so any drift is a logic bug, not
rounding.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Parser, ParserConfig, Schema, make_csv_dfa, make_log_dfa, make_simple_dfa
from repro.core import backends as backends_mod
from repro.core.streaming import StreamingParser

DFAS = {
    "csv": make_csv_dfa,
    "simple": make_simple_dfa,
    "log": make_log_dfa,
}

# Inputs exercise quotes/brackets where the DFA supports them, plus empty
# fields, signed ints, exponent floats, valid/invalid dates (leap years,
# day-in-month, time-of-day), overflowing ints, and a trailing unterminated
# record.  Every dtype the schema layer knows appears in at least one schema
# so no dtype can silently fall back to a non-backend path.
INPUTS = {
    "csv": (b'1,"a,b",3.5,2024-02-29\n'
            b'-42,"he""llo",0.25,2023-02-29\n'
            b',world,1e3,2024-04-31\n'
            b'7,x,,2024-12-31 23:59:59\n'
            b'2147483648,y,+.5,\n'
            b'8,z,1e-3,2024-06-30\n'),
    "simple": b"1,2.5\n-22,1e3\n333,junk\n,+.25\n9999999999,.\n",
    "log": b'h1 [01/Jan/2024] "GET /a b" 200\nh2 [02/Feb] "POST /c" -404\n',
}

SCHEMAS = {
    "csv": Schema.of(("i", "int32"), ("s", "str"), ("f", "float32"), ("d", "date")),
    "simple": Schema.of(("a", "int32"), ("b", "float32")),
    "log": Schema.of(("host", "str"), ("ts", "str"), ("req", "str"), ("code", "int32")),
}


def _assert_results_equal(r, q, label=""):
    for f in ("css", "col_start", "col_count", "field_offset", "field_length",
              "end_state", "last_record_end"):
        a, b = np.asarray(getattr(r, f)), np.asarray(getattr(q, f))
        assert np.array_equal(a, b), f"{label}{f}: {a} != {b}"
    assert r.values.keys() == q.values.keys()
    for name in r.values:
        for f in ("value", "valid", "empty"):
            a = np.asarray(getattr(r.values[name], f))
            b = np.asarray(getattr(q.values[name], f))
            assert np.array_equal(a, b), f"{label}values[{name}].{f}: {a} != {b}"
    for f in r.validation._fields:
        a, b = np.asarray(getattr(r.validation, f)), np.asarray(getattr(q.validation, f))
        assert np.array_equal(a, b), f"{label}validation.{f}: {a} != {b}"


def _pair(dfa_name, **kw):
    kw.setdefault("max_records", 16)
    kw.setdefault("chunk_size", 16)
    cfgs = {
        be: ParserConfig(dfa=DFAS[dfa_name](), schema=SCHEMAS[dfa_name],
                         backend=be,
                         # pin the radix partition *kernel* on the pallas
                         # side (under interpret=True "auto" would pick the
                         # jnp pass) so parity covers the whole kernel path
                         partition_impl="kernel" if be == "pallas" else "auto",
                         **kw)
        for be in ("reference", "pallas")
    }
    return Parser(cfgs["reference"]), Parser(cfgs["pallas"])


@pytest.mark.parametrize("dfa_name", sorted(DFAS))
@pytest.mark.parametrize("tagging", ("tagged", "inline", "vector"))
def test_parser_parity(dfa_name, tagging):
    ref, pal = _pair(dfa_name, tagging=tagging)
    data = INPUTS[dfa_name]
    _assert_results_equal(ref.parse(data), pal.parse(data),
                          label=f"{dfa_name}/{tagging}: ")


@pytest.mark.parametrize("dfa_name", sorted(DFAS))
def test_parser_parity_fused(dfa_name):
    """Third backend axis: the whole-pipeline megakernel
    (``fuse_pipeline=True``) must match reference bit-for-bit too.  (The
    per-tagging-mode sweep + streaming/carry variants live in
    test_fused_pipeline.py.)"""
    ref, _ = _pair(dfa_name)
    fus = Parser(ParserConfig(dfa=DFAS[dfa_name](), schema=SCHEMAS[dfa_name],
                              backend="pallas", partition_impl="kernel",
                              fuse_pipeline=True, max_records=16,
                              chunk_size=16))
    assert fus.plan.execute_path == "fused"
    data = INPUTS[dfa_name]
    _assert_results_equal(ref.parse(data), fus.parse(data),
                          label=f"{dfa_name} fused: ")


def test_parser_parity_nondefault_block_chunks():
    """Chunk counts that do not divide block_chunks exercise the pallas
    backend's pad-to-block path."""
    ref, pal = _pair("csv", chunk_size=16, block_chunks=2)
    data = INPUTS["csv"]
    assert ref.prepare(data).shape[0] % 2 == 1  # odd chunk count → padding
    _assert_results_equal(ref.parse(data), pal.parse(data))


def test_parser_parity_carry_initial_state():
    """The streaming hook: a non-default initial state (mid-quote) must give
    identical contexts on both backends."""
    ref, pal = _pair("csv")
    chunks = ref.prepare(b'b",2,3\n4,"x",5\n')
    enc = ref.cfg.dfa.state_names.index("ENC")
    r = ref.parse_chunks(jnp.asarray(chunks), initial_state=jnp.int32(enc))
    q = pal.parse_chunks(jnp.asarray(chunks), initial_state=jnp.int32(enc))
    _assert_results_equal(r, q)


def test_streaming_parity_multi_partition():
    ref, pal = _pair("csv", max_records=32)
    data = INPUTS["csv"] * 6  # several partitions with mid-record splits
    outs = []
    for p in (ref, pal):
        sp = StreamingParser(p, partition_bytes=64, max_carry_bytes=64)
        parts = [(r, n) for r, n in sp.parse_stream([data])]
        assert sp.stats.partitions > 1
        outs.append(parts)
    assert len(outs[0]) == len(outs[1])
    for (r, n_r), (q, n_q) in zip(*outs):
        assert n_r == n_q
        _assert_results_equal(r, q, label="stream: ")


@pytest.mark.parametrize("be", ("reference", "pallas"))
@pytest.mark.parametrize("tagging", ("tagged", "inline", "vector"))
def test_parser_parity_sort(be, tagging):
    """``partition_impl="sort"`` (the TPU default on pallas, shared by both
    backends) parses bit-identically to ``"scatter"``."""
    data = INPUTS["csv"]
    res = {}
    for impl in ("scatter", "sort"):
        p = Parser(ParserConfig(dfa=make_csv_dfa(), schema=SCHEMAS["csv"],
                                backend=be, partition_impl=impl,
                                tagging=tagging, max_records=16,
                                chunk_size=16))
        assert p.plan.materialize.partition_impl == impl
        res[impl] = p.parse(data)
    _assert_results_equal(res["scatter"], res["sort"],
                          label=f"{be}/{tagging}/sort: ")


@pytest.mark.parametrize("be", ("reference", "pallas"))
def test_stream_session_parity_sort(be):
    """``StreamSession`` with two batched streams (the step under ``vmap``)
    gives the same partitions with ``"sort"`` as with ``"scatter"``."""
    from repro.core.streaming import StreamSession

    datas = [INPUTS["csv"] * 3,
             b"".join(INPUTS["csv"].splitlines(True)[::-1])]
    outs = {}
    for impl in ("scatter", "sort"):
        cfg = ParserConfig(dfa=make_csv_dfa(), schema=SCHEMAS["csv"],
                           backend=be, partition_impl=impl, max_records=16,
                           chunk_size=16)
        sess = StreamSession(Parser(cfg), partition_bytes=64,
                             max_carry_bytes=64, n_streams=2)
        outs[impl] = list(sess.parse_streams([[d] for d in datas]))
    assert len(outs["scatter"]) == len(outs["sort"]) > 2
    for (s, r, n), (s2, q, n2) in zip(outs["scatter"], outs["sort"]):
        assert (s, n) == (s2, n2)
        _assert_results_equal(r, q, label=f"{be}/stream{s}/sort: ")


def test_distributed_parity():
    import jax
    from jax.sharding import Mesh
    from repro.core.distributed import DistributedParser

    data = INPUTS["csv"] * 4
    mesh = Mesh(np.array(jax.devices()), ("data",))
    shards = {}
    for be in ("reference", "pallas"):
        cfg = ParserConfig(dfa=make_csv_dfa(), schema=SCHEMAS["csv"],
                           max_records=64, chunk_size=16, backend=be,
                           partition_impl="kernel" if be == "pallas" else "auto")
        dp = DistributedParser(cfg, mesh)
        shards[be] = dp.parse_chunks(dp.prepare(data))
    r, q = shards["reference"], shards["pallas"]
    ra, qa = jax.tree_util.tree_leaves(r), jax.tree_util.tree_leaves(q)
    assert len(ra) == len(qa)
    for a, b in zip(ra, qa):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (a, b)


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown parser backend"):
        ParserConfig(dfa=make_simple_dfa(), schema=SCHEMAS["simple"],
                     max_records=4, backend="nope")


def test_registry_lists_both_backends():
    assert {"reference", "pallas"} <= set(backends_mod.available_backends())
