"""Differential fuzzing: both parser backends vs the Python csv/int/float
oracle (the harness §3.3's kernel completion is hardened by).

Random CSV tables — quoted fields, escaped quotes, embedded newlines, empty
and missing fields, signed/overflowing ints, exponent floats, valid and
invalid datetimes, unterminated tails — are parsed end-to-end on
``backend="reference"``, ``backend="pallas"``, and the pallas
whole-pipeline megakernel (``fuse_pipeline=True``), and cross-checked
field-by-field against Python's ``csv`` module + ``int()`` / ``float()`` /
``datetime`` oracles.  All backends must agree *bit-for-bit* (values,
``valid``, ``empty``, CSS, field index); the reference backend must agree
with the oracle up to documented semantics:

  * int32   — valid ⇔ ``[+-]?digits``, field ≤ ``int_width`` bytes, and
              |value| ≤ 2**31-1 (overflow clears ``valid``).
  * float32 — valid is structural (mantissa/dot/exponent shape, ≤
              ``float_width`` bytes); magnitude may round, overflow to ±inf,
              or flush to zero in the subnormal range.
  * date    — ``YYYY-MM-DD[ HH:MM:SS]`` (``T`` separator allowed) with real
              civil-calendar validation; epoch seconds within int32.
  * str     — bytes round-trip exactly (RFC 4180 unquoting/unescaping).

Two profiles: the deterministic seed sweep below runs in CI; the deep sweep
(more seeds, bigger tables) is ``-m slow``.  The hypothesis section runs
only where hypothesis is installed (CI); its CI profile is derandomized so
failures reproduce.
"""
import csv as pycsv
import datetime as dt
import io
import os
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Parser, ParserConfig, Schema, make_csv_dfa
from repro.core import typeconv
from repro.core.backends import interpret_kernels
from repro.kernels.numparse import ops as k_ops
from tests.test_backend_parity import _assert_results_equal

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # container without dev extras: numpy sweeps still run
    HAVE_HYPOTHESIS = False

INT32_MAX = 2**31 - 1
DTYPES = ("int32", "str", "float32", "date")
SCHEMA = Schema.of(("i", "int32"), ("s", "str"), ("f", "float32"), ("d", "date"))
MAX_RECORDS = 64
PAD_BYTES = 4096          # fixed byte capacity → one compiled shape per backend
CI_SEEDS = range(5)
DEEP_SEEDS = range(5, 25)

INT_RE = re.compile(r"\A[+-]?[0-9]+\Z")
FLOAT_RE = re.compile(
    r"\A[+-]?(?=[0-9]|\.[0-9])[0-9]*(\.[0-9]*)?([eE][+-]?[0-9]+)?\Z")
DATE_RE = re.compile(r"\A\d{4}-\d{2}-\d{2}([ T]\d{2}:\d{2}:\d{2})?\Z")


# ---------------------------------------------------------------------------
# oracles (documented parser semantics, in plain Python)
# ---------------------------------------------------------------------------

def oracle_int(s, width=11):
    """Returns (valid, value or None)."""
    if not INT_RE.match(s) or len(s) > width:
        return False, None
    v = int(s)
    if abs(v) > INT32_MAX:
        return False, None
    return True, v


def oracle_float_valid(s, width=24):
    return bool(FLOAT_RE.match(s)) and len(s) <= width


def oracle_date(s):
    """Returns (valid, epoch_seconds or None)."""
    if not DATE_RE.match(s):
        return False, None
    fmt = "%Y-%m-%d" if len(s) == 10 else f"%Y-%m-%d{s[10]}%H:%M:%S"
    try:
        d = dt.datetime.strptime(s, fmt).replace(tzinfo=dt.timezone.utc)
    except ValueError:  # day/month/time out of civil range
        return False, None
    return True, int(d.timestamp())


def check_float_value(s, got):
    """Value check for oracle-valid float fields, skipping the documented
    magnitude edges (overflow→inf asserted, subnormal flush skipped)."""
    want = float(s)
    if want == 0.0:
        if "e" not in s.lower():
            assert got == 0.0, (s, got)
        return
    if abs(want) > 3.5e38:
        assert np.isinf(got) and (got > 0) == (want > 0), (s, got)
        return
    if abs(want) < 1e-30:  # pow-flush zone
        return
    np.testing.assert_allclose(got, np.float32(want), rtol=2e-5, err_msg=s)


# ---------------------------------------------------------------------------
# table generator
# ---------------------------------------------------------------------------

_STR_ALPHABET = list("abcXYZ 09_-+.;")
_STR_SPICE = list('",\n')


def _gen_field(rng, dtype):
    r = rng.random()
    if r < 0.12:
        return ""  # empty / missing field
    if dtype == "int32":
        if r < 0.55:
            return str(int(rng.integers(-10**9, 10**9)))
        if r < 0.70:  # overflow boundary straddle
            return str(int(rng.integers(2**31 - 3, 2**31 + 3)) *
                       int(rng.choice([-1, 1])))
        if r < 0.85:
            return str(rng.choice(["9999999999", "12345678901", "0000000001",
                                   "+42", "-0", "007", "2147483647"]))
        return str(rng.choice(["x", "1x2", "--4", "+", "4 2", "1.5"]))
    if dtype == "float32":
        if r < 0.5:
            return f"{float(rng.normal()) * 10 ** int(rng.integers(-6, 7)):.6g}"
        if r < 0.7:
            return f"{int(rng.integers(-9999, 9999))}e{int(rng.integers(-30, 31))}"
        if r < 0.85:
            return str(rng.choice(["+.5", "-.5", "3.", "1e39", "-1e39",
                                   "1.5e+06", "0.25", "1E-3"]))
        return str(rng.choice([".", "1e", "1e+", "1.2.3", "nan", "inf", "x.5"]))
    if dtype == "date":
        y, m, d = (int(rng.integers(1902, 2038)), int(rng.integers(1, 13)),
                   int(rng.integers(1, 32)))
        if r < 0.5:
            return f"{y:04d}-{m:02d}-{d:02d}"
        if r < 0.8:
            hh, mm, ss = (int(rng.integers(0, 25)), int(rng.integers(0, 61)),
                          int(rng.integers(0, 61)))
            sep = " " if rng.random() < 0.7 else "T"
            return f"{y:04d}-{m:02d}-{d:02d}{sep}{hh:02d}:{mm:02d}:{ss:02d}"
        return str(rng.choice(["2024-02-30", "2023-02-29", "2024-04-31",
                               "2024-1-01", "junk", "2024-01-01 00:00"]))
    # str
    n = int(rng.integers(0, 13))
    alphabet = _STR_ALPHABET + (_STR_SPICE if rng.random() < 0.5 else [])
    return "".join(str(c) for c in rng.choice(alphabet, size=n))


def make_table(seed, n_rows):
    rng = np.random.default_rng(seed)
    rows = [[_gen_field(rng, d) for d in DTYPES] for _ in range(n_rows)]
    buf = io.StringIO()
    pycsv.writer(buf, quoting=pycsv.QUOTE_MINIMAL, lineterminator="\n").writerows(rows)
    text = buf.getvalue()
    if rng.random() < 0.4:
        text = text[:-1]  # unterminated tail record
    # generator/oracle self-check: csv must round-trip the exact fields
    assert [r for r in pycsv.reader(io.StringIO(text))] == rows
    return rows, text.encode()


# ---------------------------------------------------------------------------
# end-to-end differential harness
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def parsers():
    parsers = {
        be: Parser(ParserConfig(dfa=make_csv_dfa(), schema=SCHEMA,
                                max_records=MAX_RECORDS, chunk_size=64,
                                backend=be,
                                # pin the radix partition kernel on pallas so
                                # the fuzz sweep covers the kernel path
                                # (interpret-mode "auto" picks the jnp pass)
                                partition_impl="kernel" if be == "pallas" else "auto"))
        for be in ("reference", "pallas")
    }
    # third axis: the whole-pipeline megakernel (fuse_pipeline=True) joins
    # every bit-for-bit comparison
    parsers["pallas-fused"] = Parser(ParserConfig(
        dfa=make_csv_dfa(), schema=SCHEMA, max_records=MAX_RECORDS,
        chunk_size=64, backend="pallas", partition_impl="kernel",
        fuse_pipeline=True))
    assert parsers["pallas-fused"].plan.execute_path == "fused"
    return parsers


def _check_against_oracle(rows, res, parser):
    assert int(res.validation.n_records) == len(rows)
    assert bool(res.validation.ok)
    arrow = parser.to_arrow(res)
    for c, (name, dtype) in enumerate(zip("isfd", DTYPES)):
        parsed = res.values[name]
        valid = np.asarray(parsed.valid)
        empty = np.asarray(parsed.empty)
        values = np.asarray(parsed.value)
        for r, row in enumerate(rows):
            s = row[c]
            assert bool(empty[r]) == (s == ""), (r, name, s)
            if dtype == "int32":
                want_ok, want = oracle_int(s)
                assert bool(valid[r]) == want_ok, (r, s)
                if want_ok:
                    assert int(values[r]) == want, (r, s)
            elif dtype == "float32":
                want_ok = oracle_float_valid(s)
                assert bool(valid[r]) == want_ok, (r, s)
                if want_ok:
                    check_float_value(s, values[r])
            elif dtype == "date":
                want_ok, want = oracle_date(s)
                assert bool(valid[r]) == want_ok, (r, s)
                if want_ok:
                    assert int(values[r]) == want, (r, s)
            else:  # str round-trips exactly through the CSS
                a = arrow[name]
                got = bytes(a["data"][a["offsets"][r]: a["offsets"][r + 1]])
                assert got == s.encode(), (r, s, got)


def _run_differential(parsers, seed, n_rows):
    rows, data = make_table(seed, n_rows)
    assert len(data) + 1 <= PAD_BYTES
    chunks = jnp.asarray(parsers["reference"].prepare(data, pad_to=PAD_BYTES))
    ref = parsers["reference"].parse_chunks(chunks)
    pal = parsers["pallas"].parse_chunks(chunks)
    fus = parsers["pallas-fused"].parse_chunks(chunks)
    _assert_results_equal(ref, pal, label=f"seed={seed}: ")  # bit-for-bit
    _assert_results_equal(ref, fus, label=f"seed={seed} fused: ")
    _check_against_oracle(rows, ref, parsers["reference"])


@pytest.mark.parametrize("seed", CI_SEEDS)
def test_differential_fuzz_ci(parsers, seed):
    """Deterministic CI profile: fixed seeds, fixed shapes (one compile)."""
    _run_differential(parsers, seed, n_rows=24)


@pytest.mark.slow
@pytest.mark.parametrize("seed", DEEP_SEEDS)
def test_differential_fuzz_deep(parsers, seed):
    _run_differential(parsers, seed, n_rows=40)


# ---------------------------------------------------------------------------
# hypothesis column-level differential (runs where hypothesis is installed)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    settings.register_profile(
        "fuzz_ci", max_examples=25, derandomize=True, deadline=None,
        suppress_health_check=[HealthCheck.too_slow])
    settings.register_profile(
        "fuzz_deep", max_examples=200, deadline=None,
        suppress_health_check=[HealthCheck.too_slow])
    settings.load_profile(os.environ.get("FUZZ_PROFILE", "fuzz_ci"))

    N_FIELDS = 24      # fixed field count → fixed shapes → one compile
    CSS_BYTES = 512

    def _pack_fixed(strs):
        """Pad to N_FIELDS fields / CSS_BYTES bytes so shapes stay constant."""
        strs = (list(strs) + [""] * N_FIELDS)[:N_FIELDS]
        blob = "".join(strs).encode()
        lens = np.asarray([len(s) for s in strs], np.int32)
        offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
        css = np.zeros(CSS_BYTES, np.uint8)
        css[: len(blob)] = np.frombuffer(blob, np.uint8)
        return jnp.asarray(css), jnp.asarray(offs), jnp.asarray(lens), strs

    int_text = st.one_of(
        st.integers(-10**12, 10**12).map(str),
        st.from_regex(r"\A[+-]?[0-9]{1,12}\Z"),
        st.sampled_from(["", "+", "x1", "1 2", "007", "2147483648"]),
    )
    float_text = st.one_of(
        # ±1e30 as float32 bounds: hypothesis refuses bounds a width-32
        # float cannot represent exactly
        st.floats(allow_nan=False, allow_infinity=False, width=32,
                  min_value=float(np.float32(-1e30)),
                  max_value=float(np.float32(1e30))).map(lambda v: f"{v:.6g}"),
        st.from_regex(r"\A[+-]?[0-9]{1,7}(\.[0-9]{0,6})?(e[+-]?[12]?[0-9])?\Z"),
        st.sampled_from(["", ".", "+.5", "1e", "1e+", "3.", "1e39"]),
    )
    date_text = st.one_of(
        st.tuples(st.integers(1902, 2037), st.integers(1, 13),
                  st.integers(1, 31)).map(lambda t: "%04d-%02d-%02d" % t),
        st.tuples(st.integers(1902, 2037), st.integers(1, 12),
                  st.integers(1, 28), st.integers(0, 24), st.integers(0, 60),
                  st.integers(0, 60)).map(
                      lambda t: "%04d-%02d-%02d %02d:%02d:%02d" % t),
        st.sampled_from(["", "junk", "2024-02-30", "2024-01-01T00:00:00"]),
    )

    @given(st.lists(int_text, min_size=1, max_size=N_FIELDS))
    def test_hypothesis_int_differential(strs):
        css, offs, lens, strs = _pack_fixed(strs)
        ref = typeconv.parse_int(css, offs, lens, width=11)
        pal = k_ops.parse_int_column(css, offs, lens, width=11,
                                     interpret=interpret_kernels())
        np.testing.assert_array_equal(np.asarray(ref.valid), np.asarray(pal.valid))
        ok = np.asarray(ref.valid)
        np.testing.assert_array_equal(np.asarray(ref.value)[ok],
                                      np.asarray(pal.value)[ok])
        for s, v, got in zip(strs, ok, np.asarray(ref.value)):
            want_ok, want = oracle_int(s)
            assert bool(v) == want_ok, s
            if want_ok:
                assert int(got) == want, s

    @given(st.lists(float_text, min_size=1, max_size=N_FIELDS))
    def test_hypothesis_float_differential(strs):
        css, offs, lens, strs = _pack_fixed(strs)
        ref = typeconv.parse_float(css, offs, lens, width=24)
        pal = k_ops.parse_float_column(css, offs, lens, width=24,
                                       interpret=interpret_kernels())
        np.testing.assert_array_equal(np.asarray(ref.valid), np.asarray(pal.valid))
        ok = np.asarray(ref.valid)
        np.testing.assert_array_equal(np.asarray(ref.value)[ok],
                                      np.asarray(pal.value)[ok])
        for s, v, got in zip(strs, ok, np.asarray(ref.value)):
            assert bool(v) == oracle_float_valid(s), s
            if v:
                check_float_value(s, got)

    @given(st.lists(date_text, min_size=1, max_size=N_FIELDS))
    def test_hypothesis_date_differential(strs):
        css, offs, lens, strs = _pack_fixed(strs)
        ref = typeconv.parse_date(css, offs, lens)
        pal = k_ops.parse_date_column(css, offs, lens, interpret=interpret_kernels())
        np.testing.assert_array_equal(np.asarray(ref.valid), np.asarray(pal.valid))
        np.testing.assert_array_equal(np.asarray(ref.value), np.asarray(pal.value))
        for s, v, got in zip(strs, np.asarray(ref.valid), np.asarray(ref.value)):
            want_ok, want = oracle_date(s)
            assert bool(v) == want_ok, s
            if want_ok:
                assert int(got) == want, s


# ---------------------------------------------------------------------------
# per-format differential fuzz (format registry × tests/oracles)
# ---------------------------------------------------------------------------
# Random *well-formed* text per dialect; expected output comes from the
# format's sequential oracle (tests/oracles/), so the generators only have
# to stay inside the dialect — they never track rows themselves.  All three
# backends must agree bit-for-bit; reference must match the oracle.

from repro.core import formats as formats_mod  # noqa: E402
from tests import oracles  # noqa: E402,F401 — attaches oracles to the registry

FORMAT_FUZZ = ("jsonl", "zone", "clf")
FORMAT_CI_SEEDS = range(3)
FORMAT_DEEP_SEEDS = range(3, 13)


def _join_tok(rng, alphabet, lo=1, hi=9):
    return "".join(str(c) for c in rng.choice(alphabet, size=int(rng.integers(lo, hi))))


def _j_string(rng):
    """A depth-1 JSONL string: structural bytes and raw escapes inside."""
    out = []
    for _ in range(int(rng.integers(0, 9))):
        r = rng.random()
        if r < 0.15:
            out.append("\\" + str(rng.choice(['"', "n", "\\", "t"])))
        elif r < 0.5:
            out.append(str(rng.choice([",", ":", "{", "}", "[", "]", " "])))
        else:
            out.append(str(rng.choice(list("abcXYZ09_-+."))))
    return '"' + "".join(out) + '"'


def _j_nested(rng, levels):
    """Raw nested subtext; bounded depth, closers not matched by type."""
    items = []
    for _ in range(int(rng.integers(0, 3))):
        r = rng.random()
        if r < 0.25 and levels > 1:
            items.append(_j_nested(rng, levels - 1))
        elif r < 0.55:
            items.append(_j_string(rng))
        else:
            items.append(str(int(rng.integers(-99, 100))))
    o, c = [("{", "}"), ("[", "]")][int(rng.integers(0, 2))]
    return o + str(rng.choice([", ", ",", " , "])).join(items) + c


def make_jsonl_text(seed, n_rows):
    rng = np.random.default_rng([seed, 1])
    sp = lambda: " " * int(rng.integers(0, 2))  # noqa: E731
    lines = []
    for _ in range(n_rows):
        idv = str(rng.choice([str(int(rng.integers(-10**10, 10**10))), "007",
                              "2147483648", "true", "null", "0"]))
        r = rng.random()
        if r < 0.5:
            name = _j_string(rng)
        elif r < 0.8:
            name = _j_nested(rng, levels=3)  # value opens depth 2 of max 4
        else:
            name = str(rng.choice(["null", "true", "12"]))
        score = str(rng.choice([f"{float(rng.normal()):.4g}", ".5", "2e3",
                                "1e39", "3.", "x", "-0.25"]))
        lines.append("{" + sp() + f'"id"{sp()}:{sp()}{idv}{sp()},{sp()}'
                     f'"name"{sp()}:{sp()}{name}{sp()},{sp()}'
                     f'"score"{sp()}:{sp()}{score}' + sp() + "}" + sp())
        if rng.random() < 0.15:
            lines.append(str(rng.choice(["", " ", "  "])))  # blank: no record
    text = "\n".join(lines) + "\n"
    if rng.random() < 0.3:
        text = text.rstrip("\n ")  # unterminated tail record
    return text.encode()


def make_zone_text(seed, n_rows):
    rng = np.random.default_rng([seed, 2])
    ws = lambda: "".join(  # noqa: E731
        str(rng.choice([" ", "\t"])) for _ in range(int(rng.integers(1, 3))))
    tok = lambda: _join_tok(rng, list("abcdXZ0189._-"))  # noqa: E731
    lines = []
    for _ in range(n_rows):
        if rng.random() < 0.2:
            lines.append(str(rng.choice(["", " ", ";full-line comment"])))
        ttl = str(rng.choice([str(int(rng.integers(0, 10**10))), "0042",
                              "2147483647", tok()]))
        toks = [tok(), ttl, str(rng.choice(["IN", "CH", "HS"])),
                str(rng.choice(["A", "TXT", "MX", "CNAME"])), tok()]
        lo = hi = None
        if rng.random() < 0.4:  # parenthesize a span: record spans lines
            i = int(rng.integers(1, 5))
            j = int(rng.integers(i, 5))
            toks = toks[:i] + ["("] + toks[i:j + 1] + [")"] + toks[j + 1:]
            lo, hi = i, j + 2
        out = []
        for k, t in enumerate(toks):
            out.append(t)
            if k == len(toks) - 1:
                break
            in_paren = lo is not None and lo <= k < hi
            near_paren = t in "()" or toks[k + 1] in "()"
            r = rng.random()
            if in_paren and r < 0.2:
                out.append(ws() + f";c{k}\n" + ws())  # in-paren comment
            elif in_paren and r < 0.5:
                out.append("\n" + ws())  # newline-as-whitespace
            elif near_paren and r < 0.65:
                out.append("")  # parens may abut field content
            else:
                out.append(ws())
        line = "".join(out)
        if rng.random() < 0.2:
            line += str(rng.choice(["", " "])) + ";trailing"
        lines.append(line)
    text = "\n".join(lines) + "\n"
    if rng.random() < 0.3:
        text = text.rstrip("\n\t ;gnilart")  # unterminated tail
    return text.encode()


def make_clf_text(seed, n_rows):
    rng = np.random.default_rng([seed, 3])
    lines = []
    for _ in range(n_rows):
        if rng.random() < 0.08:
            lines.append("")  # blank line: a record with one empty field
            continue
        host = _join_tok(rng, list("abcXYZ019.-_"))
        if rng.random() < 0.1:
            host += "]" + host  # stray ']' outside scopes is data
        ts_body = _join_tok(rng, list("abc019/: "), 1, 14)
        if rng.random() < 0.1:
            ts_body += '"ignored'  # '"' inside [...] is dropped
        if rng.random() < 0.08:
            ts_body += "\n "  # newline inside [...] is data
        req_body = _join_tok(rng, list("GETPOST /abc?=_."), 1, 14)
        if rng.random() < 0.1:
            req_body += str(rng.choice(["[", "]"]))  # brackets in quotes: data
        if rng.random() < 0.08:
            req_body += "\nx"  # newline inside quotes is data
        code = str(rng.choice([str(int(rng.integers(-999, 1000))), "200",
                               "40x", ""]))
        sep = "  " if rng.random() < 0.1 else " "  # runs mint empty fields
        lines.append(sep.join([host, f"[{ts_body}]", f'"{req_body}"', code]))
    text = "\n".join(lines) + "\n"
    if rng.random() < 0.3:
        text = text[:-1]
    return text.encode()


FORMAT_GENERATORS = {
    "jsonl": make_jsonl_text,
    "zone": make_zone_text,
    "clf": make_clf_text,
}


def _run_format_differential(fmt, seed, n_rows):
    # Late import: test_format_conformance imports this module for the typed
    # oracles, so the shared checker/parser cache loads at call time.
    from tests.test_format_conformance import (
        BACKENDS, _check_against_oracle, parser_for)
    spec = formats_mod.get_format(fmt)
    data = FORMAT_GENERATORS[fmt](seed, n_rows)
    records = spec.oracle(data)
    assert len(records) <= MAX_RECORDS and len(data) + 1 <= PAD_BYTES
    ps = {be: parser_for(fmt, be, spec.tagging) for be in BACKENDS}
    chunks = jnp.asarray(ps["reference"].prepare(data, pad_to=PAD_BYTES))
    ref = ps["reference"].parse_chunks(chunks)
    pal = ps["pallas"].parse_chunks(chunks)
    fus = ps["pallas-fused"].parse_chunks(chunks)
    _assert_results_equal(ref, pal, label=f"{fmt} seed={seed}: ")
    _assert_results_equal(ref, fus, label=f"{fmt} seed={seed} fused: ")
    _check_against_oracle(ref, ps["reference"], records)


@pytest.mark.parametrize("fmt", FORMAT_FUZZ)
@pytest.mark.parametrize("seed", FORMAT_CI_SEEDS)
def test_format_fuzz_ci(fmt, seed):
    """Deterministic CI profile: fixed seeds, fixed shapes (one compile per
    format × backend, shared with the conformance suite's parser cache)."""
    _run_format_differential(fmt, seed, n_rows=16)


@pytest.mark.slow
@pytest.mark.parametrize("fmt", FORMAT_FUZZ)
@pytest.mark.parametrize("seed", FORMAT_DEEP_SEEDS)
def test_format_fuzz_deep(fmt, seed):
    _run_format_differential(fmt, seed, n_rows=24)
