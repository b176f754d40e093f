"""The plain reference and the generators it is held against."""
import csv
import io

import numpy as np
import pytest

from cpu_cells import REPO, config
from benchlib import cells, oracle, pieces


@pytest.mark.parametrize("data,want", [
    (b'a,b\n1,2\n', [[b"a", b"b"], [b"1", b"2"]]),
    (b'1,"x,y",3\n', [[b"1", b"x,y", b"3"]]),
    (b'1,"say ""hi""",3\n', [[b"1", b'say "hi"', b"3"]]),
    (b'1,"two\nlines",3\n4,5,6', [[b"1", b"two\nlines", b"3"], [b"4", b"5", b"6"]]),
    (b'"",,\n', [[b"", b"", b""]]),
    (b'a,b\r\nc,d\r\n', [[b"a", b"b"], [b"c", b"d"]]),
])
def test_oracle_parses_quotes_doubled_quotes_and_newlines(data, want):
    assert oracle.parse(data) == want


def test_oracle_refuses_junk_after_a_closing_quote():
    with pytest.raises(ValueError):
        oracle.parse(b'"a"b,c\n')


def _block(name, seed, nbytes=200_000):
    cfg = config(name)
    gen = cells.load_module(REPO, "gen", cfg["generator"]["name"])
    return cfg, gen.make(seed, nbytes, cfg["generator"])


@pytest.mark.parametrize("name", ["yelp_reviews_csv", "nyc_taxi_csv"])
def test_generated_records_parse_as_python_csv(name):
    cfg, (data, rec_end, str_bytes) = _block(name, 3)
    rows = list(csv.reader(io.StringIO(data.decode(), newline="")))
    recs = oracle.parse(data)
    assert [[f.decode() for f in r] for r in recs] == rows
    assert len(recs) == len(rec_end) and rec_end[-1] == len(data)
    assert all(data[e - 1] == 0x0A for e in rec_end)
    schema = cfg["schema"]
    assert all(len(r) == len(schema) for r in recs)
    strs = [c for c, (_, dtype) in enumerate(schema) if dtype == "str"]
    assert str_bytes == sum(len(r[c]) for r in recs for c in strs)
    for c, (_, dtype) in enumerate(schema):
        if dtype != "str":
            assert all(oracle.CONVERT[dtype](r[c]) is not None for r in recs)
    mean = len(data) / len(recs)
    assert abs(mean - cfg["generator"]["record_bytes"]) < 0.02 * mean


def test_yelp_records_hold_quotes_newlines_and_ids():
    _cfg, (data, _e, _s) = _block("yelp_reviews_csv", 4)
    recs = oracle.parse(data)
    assert any(b'"' in r[7] for r in recs) and any(b"\n" in r[7] for r in recs)
    assert all(len(r[k]) == 22 for r in recs for k in range(3))
    assert all(len(r[8]) == 19 for r in recs)


def test_taxi_fields_follow_the_2018_formats():
    _cfg, (data, _e, _s) = _block("nyc_taxi_csv", 5)
    recs = oracle.parse(data)
    assert any(r[4].startswith(b".") for r in recs)
    assert all(len(r[4].split(b".")[1]) == 2 for r in recs)
    assert {r[6] for r in recs} <= {b"N", b"Y"}
    assert {r[12] for r in recs} == {b"0.5"} and {r[15] for r in recs} == {b"0.3"}
    assert any(b"." not in r[10] for r in recs)


@pytest.mark.parametrize("name", ["yelp_reviews_csv", "nyc_taxi_csv"])
def test_every_seed_gets_the_same_record_sizes(name):
    _cfg, (a, ea, _) = _block(name, 2**31 + 9)
    _cfg, (b, eb, _) = _block(name, 17)
    assert a != b and len(a) == len(b)
    assert sorted(np.diff(ea, prepend=0)) == sorted(np.diff(eb, prepend=0))


def test_same_seed_same_bytes():
    assert _block("nyc_taxi_csv", 2**31 + 9)[1][0] == _block("nyc_taxi_csv", 2**31 + 9)[1][0]


def test_numbers_print_as_the_sources_do():
    got = pieces.rows([pieces.cents(np.array([0, 5, 50, 1430, 1400, 1235]), "short")])
    assert got[0].tobytes() == b"0" + b"0.05" + b"0.5" + b"14.3" + b"14" + b"12.35"
    got = pieces.rows([pieces.cents(np.array([0, 50, 270, 1230]), "2dp")])
    assert got[0].tobytes() == b".00" + b".50" + b"2.70" + b"12.30"
    got = pieces.datetime(np.array([1514766065]))
    assert got[0].tobytes() == b"2018-01-01 00:21:05"


def test_typed_values_follow_python():
    assert oracle.to_int(b"-42") == -42
    assert oracle.to_date(b"2018-03-01 00:00:01") == 1519862401
    assert oracle.to_float(b"29.99") == float(np.float32(29.99))
    assert oracle.to_float(b".50") == 0.5


def test_bfloat16_rounds_to_nearest_even():
    bf16 = cells.load_module(REPO, "controls", "bfloat16_floats").bf16
    x = np.array([1.0, 1.00390625, 1.01171875, 3.14159], np.float32)
    got = bf16(x)
    assert got[0] == 1.0 and got[1] == 1.0 and got[2] == 1.015625
    assert abs(got[3] - 3.140625) < 1e-7
