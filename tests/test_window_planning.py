"""Windowed-DMA fused numparse: window planning + kernel coverage.

The fused gather+convert kernels DMA one contiguous CSS window per row
block instead of holding the whole CSS in VMEM (``ops.plan_css_windows`` +
``parse_*_fields_windowed``).  These tests pin the plan geometry (aligned
starts, tight windows, monotone/fits detection), the degenerate shapes
(block-boundary fields, straddling fields, empty and all-empty columns,
multi-tile mega-field fallback, non-monotone offsets), and the acceptance
bar that the windowed path still issues no XLA gather outside pallas_call.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jaxpr_utils import gathers_outside_pallas as _gathers_outside_pallas
from repro.core import typeconv
from repro.core.backends import interpret_kernels
from repro.kernels.numparse import numparse
from repro.kernels.numparse import ops as k_ops

ALIGN = numparse.WINDOW_ALIGN


def _pack_css(strs):
    """Concatenate field strings into a CSS + (offset, length) index."""
    lens = np.asarray([len(s) for s in strs], np.int32)
    offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    css = np.frombuffer("".join(strs).encode(), np.uint8)
    if css.size == 0:
        css = np.zeros(1, np.uint8)
    return jnp.asarray(css), jnp.asarray(offs), jnp.asarray(lens)


def _plan(offs, lens, br, width, wt, css_len):
    pad = (-offs.shape[0]) % br
    offs = np.pad(np.asarray(offs), (0, pad))
    lens = np.pad(np.asarray(lens), (0, pad))
    return k_ops.plan_css_windows(
        jnp.asarray(offs, jnp.int32), jnp.asarray(lens, jnp.int32),
        rows_per_block=br, width=width, window_bytes=wt, css_len=css_len,
    )


def _per_row(fn, css, offs, lens, **kw):
    """A per-row-window kernel's result as a ``typeconv.Parsed``."""
    val, ok = fn(css, offs, lens, interpret=interpret_kernels(), **kw)
    empty = lens == 0
    return typeconv.Parsed(val, ok & ~empty, empty)


def _assert_parsed_equal(got, want, msg=""):
    for f in ("value", "valid", "empty"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
            err_msg=f"{msg}: {f}")


# ---------------------------------------------------------------------------
# plan geometry
# ---------------------------------------------------------------------------

def test_plan_window_starts_aligned_and_tight():
    strs = [str(1000 + i) for i in range(64)]  # 4 bytes each, offsets 0,4,8…
    css, offs, lens = _pack_css(strs)
    ws, rel, fits = _plan(offs, lens, br=16, width=11, wt=512,
                          css_len=int(css.shape[0]))
    ws = np.asarray(ws)
    assert bool(fits)
    assert (ws % ALIGN == 0).all()
    # block b starts at field 16b → offset 64b, aligned down to 128-multiples
    np.testing.assert_array_equal(ws, (np.arange(4) * 64) // ALIGN * ALIGN)
    # relative offsets must reproduce the absolute ones
    np.testing.assert_array_equal(
        np.asarray(rel) + np.repeat(ws, 16), np.asarray(offs))


def test_plan_block_boundary_windows():
    """Fields exactly at row-block boundaries stay inside their block's
    window — including the last field of block b and first of block b+1
    sharing a CSS byte neighbourhood."""
    strs = ["%06d" % i for i in range(256)]  # 6-byte fields, br divides evenly
    css, offs, lens = _pack_css(strs)
    ws, rel, fits = _plan(offs, lens, br=64, width=11, wt=1024,
                          css_len=int(css.shape[0]))
    assert bool(fits)
    ws, rel = np.asarray(ws), np.asarray(rel)
    offs, lens = np.asarray(offs), np.asarray(lens)
    for b in range(4):
        for r in range(64):
            i = b * 64 + r
            assert ws[b] <= offs[i], (b, i)
            assert offs[i] + 11 <= ws[b] + 1024, (b, i)
            assert rel[i] == offs[i] - ws[b]


def test_plan_detects_mega_field_overflow():
    strs = [str(i) for i in range(32)] + ["9" * 4000] + [str(i) for i in range(31)]
    css, offs, lens = _pack_css(strs)
    _, _, fits = _plan(offs, lens, br=16, width=11, wt=256,
                       css_len=int(css.shape[0]))
    assert not bool(fits)
    # a tile large enough for the straddle fits again
    _, _, fits2 = _plan(offs, lens, br=16, width=11, wt=8192,
                        css_len=int(css.shape[0]))
    assert bool(fits2)


def test_plan_detects_non_monotone_offsets():
    css = jnp.zeros(1024, jnp.uint8)
    offs = np.arange(64, dtype=np.int32) * 4
    offs[10] = 900  # jumps forward…
    offs[11] = 40   # …then back: violates sortedness
    lens = np.full(64, 3, np.int32)
    _, _, fits = _plan(jnp.asarray(offs), jnp.asarray(lens), br=64, width=11,
                       wt=2048, css_len=1024)
    assert not bool(fits)


def test_plan_empty_fields_inherit_running_offset():
    """Empty fields carry offset 0 from the field index; the plan must not
    let them drag a late block's window back to the CSS start."""
    strs = []
    for i in range(128):
        strs.append("" if i % 3 == 0 else str(10000 + i))
    css, offs, lens = _pack_css(strs)
    offs = np.asarray(offs).copy()
    offs[np.asarray(lens) == 0] = 0  # what field_index emits for absent/empty
    ws, rel, fits = _plan(jnp.asarray(offs), lens, br=32, width=11, wt=512,
                          css_len=int(css.shape[0]))
    assert bool(fits)
    ws = np.asarray(ws)
    assert (np.diff(ws) >= 0).all()
    assert ws[-1] > 0  # late windows moved forward despite the zero offsets


def test_plan_leading_empty_does_not_drag_window_to_css_start():
    """An empty field in a column's FIRST record must not seed block 0's
    window at CSS offset 0 when the column's bytes live far into the CSS —
    that would overflow the tile and silently disable windowing."""
    col_base = 100_000  # the column's segment starts deep in the CSS
    offs = np.zeros(32, np.int32)
    lens = np.zeros(32, np.int32)
    pos = col_base
    for i in range(32):
        if i % 7 == 0:
            continue  # empty field: offset stays 0 (what field_index emits)
        offs[i] = pos
        lens[i] = 5
        pos += 5
    ws, rel, fits = _plan(jnp.asarray(offs), jnp.asarray(lens), br=32,
                          width=11, wt=512, css_len=col_base + 200)
    assert bool(fits)  # the window seeds from the first non-empty offset…
    assert int(np.asarray(ws)[0]) == col_base // ALIGN * ALIGN  # …not from 0


def test_per_row_window_fallback_handles_arbitrary_offsets():
    """The windowed path's fallback (per-row windows) parses correctly
    with non-monotone offsets and mega-fields — the shapes the
    block-window invariant cannot cover."""
    strs = [str(i * 31) for i in range(64)] + ["8" * 900]
    css, offs, lens = _pack_css(strs)
    # shuffle the index: rows no longer sorted by offset
    perm = np.random.default_rng(3).permutation(len(strs))
    offs = jnp.asarray(np.asarray(offs)[perm])
    lens = jnp.asarray(np.asarray(lens)[perm])
    got = k_ops._fused_column(
        functools.partial(numparse.parse_int_fields_windowed, width=11),
        functools.partial(numparse.parse_int_fields_per_row, width=11),
        css, offs, lens, 11, jnp.int32, 0, 0, interpret_kernels(),
    )
    ref = typeconv.parse_int(css, offs, lens, width=11)
    np.testing.assert_array_equal(np.asarray(got.valid), np.asarray(ref.valid))
    ok = np.asarray(got.valid)
    np.testing.assert_array_equal(np.asarray(got.value)[ok],
                                  np.asarray(ref.value)[ok])


def test_fused_column_vmap_keeps_each_lanes_choice():
    """Under ``vmap`` each lane keeps its own windowed/per-row choice: a
    batch mixing a mega-field lane with a well-formed lane equals the
    lanes parsed alone, and a batch where every lane fits stays a branch
    (``cond``) instead of a select that would run the per-row kernel."""
    good = [str(i * 7) for i in range(60)] + ["12"] * 4
    bad = [str(i) for i in range(30)] + ["7" * 3000] + [str(-i) for i in range(33)]
    lanes = [_pack_css(s) for s in (good, bad)]
    n = max(int(c.shape[0]) for c, _, _ in lanes)
    css = jnp.stack([jnp.pad(c, (0, n - c.shape[0])) for c, _, _ in lanes])
    offs = jnp.stack([o for _, o, _ in lanes])
    lens = jnp.stack([ln for _, _, ln in lanes])
    fn = lambda c, o, ln: k_ops.parse_int_column_fused(
        c, o, ln, window_rows=16, window_bytes=256,
        interpret=interpret_kernels())
    got = jax.vmap(fn)(css, offs, lens)
    for i in range(2):
        want = fn(css[i], offs[i], lens[i])
        _assert_parsed_equal(jax.tree.map(lambda x: x[i], got), want,
                             f"lane {i}")
    ref = typeconv.parse_int(css[1], offs[1], lens[1], width=11)
    np.testing.assert_array_equal(np.asarray(got.valid[1]),
                                  np.asarray(ref.valid))
    jx = jax.make_jaxpr(jax.vmap(fn))(css, offs, lens)
    assert re.search(r"\bcond\[", str(jx)), "batched fallback lost its branch"


def test_auto_window_bytes_geometry():
    wt = k_ops.auto_window_bytes(512, 11)
    assert wt % ALIGN == 0
    assert wt >= 512 * 12 + 11  # every ≤11-byte field + terminator fits
    # explicit sizes are rounded up to alignment and floored sanely
    br, wt2 = k_ops._resolve_window(16, 100, 11, 1000)
    assert br == 16 and wt2 % ALIGN == 0 and wt2 >= 11 + ALIGN


# ---------------------------------------------------------------------------
# windowed kernels vs per-row windows vs typeconv
# ---------------------------------------------------------------------------

def _mixed_cases(rng, rows):
    ints, floats, dates = [], [], []
    for _ in range(rows):
        u = rng.random()
        if u < 0.15:
            junk = rng.choice(["", "x1y", "+", ".", "1e", "9" * 12, "2024-13-01"])
            ints.append(junk); floats.append(junk); dates.append(junk)
            continue
        ints.append(str(int(rng.integers(-(2**33), 2**33))))
        floats.append(f"{rng.normal() * 10.0 ** int(rng.integers(-6, 7)):.6g}")
        y, m, d = rng.integers(1970, 2038), rng.integers(1, 13), rng.integers(1, 29)
        dates.append(f"{y:04d}-{m:02d}-{d:02d}" if rng.random() < 0.5 else
                     f"{y:04d}-{m:02d}-{d:02d} {rng.integers(0, 24):02d}:"
                     f"{rng.integers(0, 60):02d}:{rng.integers(0, 60):02d}")
    return ints, floats, dates


@pytest.mark.parametrize("rows,window_rows", [(500, 32), (512, 512), (33, 8)])
def test_windowed_matches_wholecss_and_typeconv(rows, window_rows):
    """The windowed kernels equal the per-row-window kernels (the other
    CSS-indexing family; the whole-CSS kernels this once compared against
    are gone) and the typeconv oracle."""
    ints, floats, dates = _mixed_cases(np.random.default_rng(rows), rows)
    cases = [
        (ints, k_ops.parse_int_column_fused,
         functools.partial(numparse.parse_int_fields_per_row, width=11),
         lambda c, o, l: typeconv.parse_int(c, o, l, width=11)),
        (floats, k_ops.parse_float_column_fused,
         functools.partial(numparse.parse_float_fields_per_row, width=24),
         lambda c, o, l: typeconv.parse_float(c, o, l, width=24)),
        (dates, k_ops.parse_date_column_fused,
         numparse.parse_date_fields_per_row, typeconv.parse_date),
    ]
    for strs, fused, per_row, oracle in cases:
        css, offs, lens = _pack_css(strs)
        got = fused(css, offs, lens, window_rows=window_rows,
                    interpret=interpret_kernels())
        other = _per_row(per_row, css, offs, lens)
        _assert_parsed_equal(got, other, f"{fused.__name__} windowed vs per-row")
        ref = oracle(css, offs, lens)
        np.testing.assert_array_equal(np.asarray(got.valid), np.asarray(ref.valid))
        np.testing.assert_array_equal(np.asarray(got.empty), np.asarray(ref.empty))
        ok = np.asarray(got.valid)
        np.testing.assert_array_equal(np.asarray(got.value)[ok],
                                      np.asarray(ref.value)[ok])


def test_windowed_field_straddles_two_row_blocks():
    """The last field of one row block extends past the next block's first
    offset: both blocks' windows must cover their own reads."""
    strs = (["%02d" % i for i in range(15)] + ["88887777"]  # long field at
            + ["%02d" % i for i in range(16)])              # a block boundary
    css, offs, lens = _pack_css(strs)
    got = k_ops.parse_int_column_fused(css, offs, lens, window_rows=16,
                                       interpret=interpret_kernels())
    ref = typeconv.parse_int(css, offs, lens, width=11)
    np.testing.assert_array_equal(np.asarray(got.valid), np.asarray(ref.valid))
    np.testing.assert_array_equal(np.asarray(got.value)[np.asarray(got.valid)],
                                  np.asarray(ref.value)[np.asarray(got.valid)])


def test_windowed_empty_and_all_empty_columns():
    # interleaved empties
    strs = ["" if i % 2 else str(i * 7) for i in range(128)]
    css, offs, lens = _pack_css(strs)
    got = k_ops.parse_int_column_fused(css, offs, lens, window_rows=16,
                                       interpret=interpret_kernels())
    other = _per_row(numparse.parse_int_fields_per_row, css, offs, lens,
                     width=11)
    _assert_parsed_equal(got, other, "interleaved empties")
    # all-empty column (every offset 0, every length 0)
    css0 = jnp.zeros(1, jnp.uint8)
    z = jnp.zeros(64, jnp.int32)
    for fused in (k_ops.parse_int_column_fused, k_ops.parse_float_column_fused,
                  k_ops.parse_date_column_fused):
        got = fused(css0, z, z, window_rows=16, interpret=interpret_kernels())
        assert not np.asarray(got.valid).any()
        assert np.asarray(got.empty).all()


def test_windowed_mega_field_falls_back_per_column():
    """A single multi-tile mega-field flips the column to per-row windows
    at run time — results stay bit-identical to the oracle and to the
    per-row kernel run directly."""
    strs = ([str(i) for i in range(100)] + ["7" * 5000]
            + [str(-i) for i in range(100)])
    css, offs, lens = _pack_css(strs)
    got = k_ops.parse_int_column_fused(css, offs, lens, window_rows=16,
                                       window_bytes=256, interpret=interpret_kernels())
    other = _per_row(numparse.parse_int_fields_per_row, css, offs, lens,
                     width=11)
    _assert_parsed_equal(got, other, "mega-field fallback")
    ref = typeconv.parse_int(css, offs, lens, width=11)
    np.testing.assert_array_equal(np.asarray(got.valid), np.asarray(ref.valid))
    assert not bool(np.asarray(got.valid)[100])  # the mega-field itself: too wide


def test_windowed_field_at_css_end():
    """Windows touching the last CSS byte rely on the tile padding, not on
    reading past the buffer."""
    strs = ["123", "-45", "678"]
    css, offs, lens = _pack_css(strs)
    got = k_ops.parse_int_column_fused(css, offs, lens, window_rows=2,
                                       interpret=interpret_kernels())
    want = k_ops.parse_int_column(css, offs, lens, interpret=interpret_kernels())
    _assert_parsed_equal(got, want, "css end")


# ---------------------------------------------------------------------------
# end-to-end through the parser + jaxpr acceptance bar
# ---------------------------------------------------------------------------

def _taxi_like_rows(n):
    return b"".join(
        b"%d,a%d,%d.%02d,2026-0%d-1%d\n"
        % (i, i, i % 1000, i % 100, i % 9 + 1, i % 9) for i in range(n))


@pytest.mark.parametrize("kw", [
    {},                                  # default: windowed, auto tile
    {"window_rows": 8},                  # many tiny windows
    {"max_window_bytes": 384},           # explicit tile
    {"window_rows": 128, "max_window_bytes": 4096},  # TPU tuner values
])
def test_parser_window_knobs_match_reference(kw):
    from repro.core import Parser, ParserConfig, Schema, make_csv_dfa

    schema = Schema.of(("id", "int32"), ("name", "str"),
                       ("price", "float32"), ("updated", "date"))
    data = _taxi_like_rows(200)
    ref = Parser(ParserConfig(dfa=make_csv_dfa(), schema=schema,
                              max_records=256)).parse(data)
    got = Parser(ParserConfig(dfa=make_csv_dfa(), schema=schema,
                              max_records=256, backend="pallas",
                              **kw)).parse(data)
    assert int(got.validation.n_records) == 200
    np.testing.assert_array_equal(np.asarray(got.css), np.asarray(ref.css))
    for c in ref.values:
        _assert_parsed_equal(got.values[c], ref.values[c], f"{kw} {c}")


def test_parser_config_window_knob_validation():
    from repro.core import ParserConfig, Schema, make_csv_dfa

    schema = Schema.of(("i", "int32"))
    for bad in (-1, -2):
        with pytest.raises(ValueError, match="window_rows"):
            ParserConfig(dfa=make_csv_dfa(), schema=schema, max_records=8,
                         backend="pallas", window_rows=bad)
    with pytest.raises(ValueError, match="max_window_bytes"):
        ParserConfig(dfa=make_csv_dfa(), schema=schema, max_records=8,
                     backend="pallas", max_window_bytes=-1)


def test_plan_records_typeconv_path():
    from repro.core import ParserConfig, Schema, get_backend, make_csv_dfa
    from repro.core import stages as stages_mod

    schema = Schema.of(("i", "int32"))
    mk = lambda **kw: stages_mod.plan_materialize(
        ParserConfig(dfa=make_csv_dfa(), schema=schema, max_records=8, **kw),
        get_backend(kw.get("backend", "reference")))
    assert mk().typeconv_path == "reference"
    assert mk(backend="pallas").typeconv_path == "fused-windowed"
    assert mk(backend="pallas", fuse_typeconv=False).typeconv_path == "unfused"


def test_windowed_kernels_issue_no_xla_gather():
    """Acceptance bar: the windowed fused path — window planning, the
    lax.cond fallback, and the kernels themselves — issues no XLA-level
    take/gather.  Covers the default config and explicit window knobs."""
    from repro.core import ParserConfig, Schema, get_backend, make_csv_dfa

    be = get_backend("pallas")
    css = jnp.zeros(100001, jnp.uint8)
    off = jnp.zeros(4096, jnp.int32)
    ln = jnp.zeros(4096, jnp.int32)
    schema = Schema.of(("i", "int32"), ("f", "float32"), ("d", "date"))
    for kw in ({}, {"window_rows": 64}, {"max_window_bytes": 512}):
        cfg = ParserConfig(dfa=make_csv_dfa(), schema=schema, max_records=64,
                           backend="pallas", **kw)
        for dtype in ("int32", "float32", "date"):
            jx = jax.make_jaxpr(
                lambda c, o, l: be.parse_field[dtype](c, o, l, cfg)
            )(css, off, ln)
            assert not _gathers_outside_pallas(jx.jaxpr), (kw, dtype)
