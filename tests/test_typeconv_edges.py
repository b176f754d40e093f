"""§3.3 semantic edge cases, pinned on every conversion path.

Three families of regressions:

  * int32 magnitude overflow — ``parse_int`` (jnp gather), the numparse
    Pallas kernel, and ``parse_int_segmented`` must all clear ``valid`` for
    values like ``9999999999`` instead of silently Horner-wrapping, and must
    agree with each other (the old ≤9- vs ≤10-digit cap inconsistency).
  * ``parse_date`` semantics — day-in-month/leap-year validation, the
    ``length==19`` time path, separator and time-of-day ranges.
  * ``parse_float`` boundaries — overflow-to-inf, lone ``.``, ``+.5``-style
    dotted signs, exponent edge shapes.

Every case asserts the reference and Pallas backends agree bit-for-bit on
values and verdicts.
"""
import datetime as dt

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import typeconv
from repro.core.backends import interpret_kernels
from repro.kernels.numparse import ops as k_ops

INT32_MAX = 2**31 - 1


def _column(strs):
    """Pack python strings into (css, offset, length) back to back."""
    lens = np.asarray([len(s) for s in strs], np.int32)
    offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    css = np.frombuffer("".join(strs).encode() or b"\x00", np.uint8)
    return jnp.asarray(css), jnp.asarray(offs), jnp.asarray(lens)


def _segmented_inputs(strs):
    total = sum(len(s) for s in strs)
    fid = np.concatenate([[i] * len(s) for i, s in enumerate(strs)] or [[0]])
    fstart = np.zeros(max(total, 1), bool)
    pos = 0
    for s in strs:
        if s:
            fstart[pos] = True
        pos += len(s)
    return jnp.asarray(fstart), jnp.asarray(fid.astype(np.int32))


# ---------------------------------------------------------------------------
# int32 overflow
# ---------------------------------------------------------------------------

INT_CASES = [
    # (text, expected_valid) — expected_value is int(text) where valid
    ("2147483647", True),
    ("-2147483647", True),
    ("+2147483647", True),
    ("2147483648", False),          # old behaviour: wrapped to -2147483648
    ("-2147483648", False),         # symmetric magnitude cap (documented)
    ("9999999999", False),          # old behaviour: wrapped silently
    ("99999999999999", False),
    ("0000000001", True),           # 10 digits, small value
    ("00000000000042", True),       # >10 digits of leading zeros still fine
    ("1410065407", True),           # what 9999999999 used to wrap to
    ("42", True),
    ("-0", True),
]


def test_parse_int_overflow_gather_and_kernel():
    strs = [s for s, _ in INT_CASES]
    css, offs, lens = _column(strs)
    width = int(lens.max())
    ref = typeconv.parse_int(css, offs, lens, width=width)
    pal = k_ops.parse_int_column(css, offs, lens, width=width,
                                 interpret=interpret_kernels())
    want_valid = np.asarray([v for _, v in INT_CASES])
    np.testing.assert_array_equal(np.asarray(ref.valid), want_valid)
    np.testing.assert_array_equal(np.asarray(pal.valid), want_valid)
    want_vals = np.asarray([int(s) for s, v in INT_CASES if v], np.int64)
    np.testing.assert_array_equal(np.asarray(ref.value)[want_valid], want_vals)
    np.testing.assert_array_equal(np.asarray(pal.value)[want_valid], want_vals)


def test_parse_int_overflow_segmented():
    strs = [s for s, _ in INT_CASES]
    css, offs, lens = _column(strs)
    fstart, fid = _segmented_inputs(strs)
    seg = typeconv.parse_int_segmented(css, fstart, fid, len(strs))
    want_valid = np.asarray([v for _, v in INT_CASES])
    np.testing.assert_array_equal(np.asarray(seg.valid), want_valid)
    want_vals = np.asarray([int(s) for s, v in INT_CASES if v], np.int64)
    np.testing.assert_array_equal(np.asarray(seg.value)[want_valid], want_vals)


def test_int_paths_reconciled_on_long_digit_runs():
    """The old caps disagreed: gather accepted ≤10 digits, segmented ≤9.
    Both now accept any digit count whose *value* fits int32."""
    strs = ["0" * 9 + "7", "0" * 12 + "3", "1" * 10, "2000000000", "2147483640"]
    css, offs, lens = _column(strs)
    width = int(lens.max())
    fstart, fid = _segmented_inputs(strs)
    gat = typeconv.parse_int(css, offs, lens, width=width)
    seg = typeconv.parse_int_segmented(css, fstart, fid, len(strs))
    pal = k_ops.parse_int_column(css, offs, lens, width=width,
                                 interpret=interpret_kernels())
    np.testing.assert_array_equal(np.asarray(gat.valid), np.asarray(seg.valid))
    np.testing.assert_array_equal(np.asarray(gat.valid), np.asarray(pal.valid))
    want_valid = np.asarray([True, True, True, True, True])
    np.testing.assert_array_equal(np.asarray(gat.valid), want_valid)
    np.testing.assert_array_equal(np.asarray(gat.value),
                                  [7, 3, 1111111111, 2000000000, 2147483640])
    np.testing.assert_array_equal(np.asarray(seg.value), np.asarray(gat.value))
    np.testing.assert_array_equal(np.asarray(pal.value), np.asarray(gat.value))


# ---------------------------------------------------------------------------
# parse_date semantics
# ---------------------------------------------------------------------------

DATE_CASES = [
    ("2024-02-29", True),            # leap year
    ("2023-02-29", False),           # not a leap year
    ("1900-02-29", False),           # century non-leap
    ("2000-02-29", True),            # 400-year leap
    ("2024-02-30", False),
    ("2024-04-31", False),           # 30-day month
    ("2024-06-31", False),
    ("2024-09-31", False),
    ("2024-11-31", False),
    ("2024-01-31", True),
    ("2024-12-31", True),
    ("2024-00-10", False),
    ("2024-13-10", False),
    ("2024-01-00", False),
    # length==19 time path
    ("2024-12-31 23:59:59", True),
    ("2024-12-31T23:59:59", True),   # ISO 8601 separator
    ("2024-12-31x23:59:59", False),
    ("2024-01-01 24:00:00", False),
    ("2024-01-01 23:60:00", False),
    ("2024-01-01 23:00:60", False),
    ("2024-01-01 00:00:00", True),
    ("2023-02-29 12:00:00", False),  # civil check applies on the time path too
    # structural
    ("2024-1-01", False),
    ("2024/01/01", False),
    ("2024-01-01 00:00", False),     # length 16: neither 10 nor 19
    ("", False),
]


def test_parse_date_semantics_both_backends():
    strs = [s for s, _ in DATE_CASES]
    css, offs, lens = _column(strs)
    ref = typeconv.parse_date(css, offs, lens)
    pal = k_ops.parse_date_column(css, offs, lens, interpret=interpret_kernels())
    want_valid = np.asarray([v for _, v in DATE_CASES])
    np.testing.assert_array_equal(np.asarray(ref.valid), want_valid,
                                  err_msg=str(strs))
    np.testing.assert_array_equal(np.asarray(pal.valid), np.asarray(ref.valid))
    np.testing.assert_array_equal(np.asarray(pal.value), np.asarray(ref.value))
    np.testing.assert_array_equal(np.asarray(pal.empty), np.asarray(ref.empty))
    # values: cross-check the valid ones against Python datetime
    for s, v, got in zip(strs, want_valid, np.asarray(ref.value)):
        if not v:
            continue
        fmt = "%Y-%m-%d" if len(s) == 10 else f"%Y-%m-%d{s[10]}%H:%M:%S"
        ts = dt.datetime.strptime(s, fmt).replace(tzinfo=dt.timezone.utc).timestamp()
        assert int(got) == int(ts), s


# ---------------------------------------------------------------------------
# parse_float boundaries
# ---------------------------------------------------------------------------

FLOAT_CASES = [
    # (text, expected_valid, expected_value or None for "don't check")
    ("1e38", True, np.float32(1e38)),
    ("1e39", True, np.float32(np.inf)),     # overflow-to-inf, still valid
    ("-1e39", True, np.float32(-np.inf)),
    ("3402823466e29", True, None),          # ~float32 max neighbourhood
    # near/below the float32 subnormal range the 10^exp pow flushes to zero
    # (XLA FTZ); both backends share the behaviour, so value is unchecked.
    ("1e-38", True, None),
    ("1e-39", True, None),
    (".", False, None),
    ("+.", False, None),
    ("+.5", True, np.float32(0.5)),
    ("-.5", True, np.float32(-0.5)),
    ("3.", True, np.float32(3.0)),
    ("1e", False, None),
    ("1e+", False, None),
    ("1E-3", True, np.float32(1e-3)),
    ("1.2.3", False, None),
    ("1e2e3", False, None),
    ("1.5e+06", True, np.float32(1.5e6)),
    ("", False, None),
    ("-", False, None),
]


def test_parse_float_boundaries_both_backends():
    strs = [s for s, _, _ in FLOAT_CASES]
    css, offs, lens = _column(strs)
    ref = typeconv.parse_float(css, offs, lens, width=24)
    pal = k_ops.parse_float_column(css, offs, lens, width=24,
                                   interpret=interpret_kernels())
    want_valid = np.asarray([v for _, v, _ in FLOAT_CASES])
    np.testing.assert_array_equal(np.asarray(ref.valid), want_valid,
                                  err_msg=str(strs))
    # bit-for-bit backend agreement on the verdicts AND the values
    np.testing.assert_array_equal(np.asarray(pal.valid), np.asarray(ref.valid))
    np.testing.assert_array_equal(np.asarray(pal.value)[want_valid],
                                  np.asarray(ref.value)[want_valid])
    for (s, v, want), got in zip(FLOAT_CASES, np.asarray(ref.value)):
        if want is None or not v:
            continue
        if np.isinf(want):
            assert got == want, (s, got)
        else:
            np.testing.assert_allclose(got, want, rtol=3e-6, err_msg=s)


def test_parse_float_inf_overflow_matches_python():
    """float32 overflow mirrors what numpy's float32 cast of python floats
    does: finite doubles beyond 3.4028235e38 land on inf."""
    strs = ["3e38", "4e38", "1e40", "-4e38"]
    css, offs, lens = _column(strs)
    ref = typeconv.parse_float(css, offs, lens, width=24)
    pal = k_ops.parse_float_column(css, offs, lens, width=24,
                                   interpret=interpret_kernels())
    np.testing.assert_array_equal(np.asarray(ref.value), np.asarray(pal.value))
    with np.errstate(over="ignore"):  # the float32 cast overflows by design
        want = [np.float32(float(s)) for s in strs]
    for s, got, w in zip(strs, np.asarray(ref.value), want):
        assert got == w, (s, got)
    assert np.isinf(np.asarray(ref.value)[1:]).all()
