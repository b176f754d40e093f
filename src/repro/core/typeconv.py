"""Type conversion: CSS symbol strings → typed columnar values (paper §3.3).

The paper assigns one GPU thread per field and escalates to block-/device-
level collaboration for long fields.  The TPU adaptation (DESIGN.md §3):

  * ``gather``      — fixed-width path: every field gathers up to ``W`` bytes
    and parses them with branchless vector arithmetic.  The analogue of
    thread-exclusive conversion; padding waste replaces warp divergence.
  * ``segmented``   — the collaboration analogue: digit accumulation is the
    associative semigroup ``(v_a, n_a) ⊕ (v_b, n_b) = (v_a·10^n_b + v_b,
    n_a + n_b)`` with field-boundary resets, so one segmented
    ``associative_scan`` over the whole CSS parses *all* integer fields of a
    column at once, regardless of individual field length — no padding, no
    skew sensitivity (exactly what block/device collaboration bought the
    paper).

Floats parse sign / integer / fraction / exponent sections with masked
Horner accumulation; dates use the days-from-civil algorithm (pure integer
arithmetic, fully vectorised).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_ZERO = ord("0")
_POW10_I32 = jnp.array([10**k for k in range(10)], jnp.int32)
#: Magnitude cap for int32 parses.  The cap is symmetric (|v| ≤ 2**31-1 for
#: either sign) so sign handling stays branchless; ``-2147483648`` — the one
#: value a sign-aware cap would additionally admit — is rejected as overflow.
INT32_MAX = 2**31 - 1
_I32_MAX = jnp.int32(INT32_MAX)


class Parsed(NamedTuple):
    value: jax.Array  # (R,) parsed values
    valid: jax.Array  # (R,) bool — parse succeeded on a present, non-empty field
    empty: jax.Array  # (R,) bool — zero-length field (NULL → default)


def gather_field_bytes(css: jax.Array, offset: jax.Array, length: jax.Array, width: int):
    """Gather each field's first ``width`` bytes: ``(R, W) uint8`` + mask.

    Out-of-range lanes read clamped positions and are masked to 0.
    """
    n = css.shape[0]
    idx = offset[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    mask = jnp.arange(width, dtype=jnp.int32)[None, :] < length[:, None]
    data = css[jnp.clip(idx, 0, n - 1)]
    return jnp.where(mask, data, 0), mask


def _sign_and_digits(bytes_w, mask):
    """Split optional leading sign; returns (sign ±1, digit bytes, digit mask)."""
    first = bytes_w[:, 0]
    has_sign = (first == ord("-")) | (first == ord("+"))
    sign = jnp.where(first == ord("-"), -1, 1).astype(jnp.int32)
    # Shift left by one where a sign is present.
    shifted = jnp.concatenate([bytes_w[:, 1:], jnp.zeros_like(bytes_w[:, :1])], axis=1)
    shifted_m = jnp.concatenate([mask[:, 1:], jnp.zeros_like(mask[:, :1])], axis=1)
    digits = jnp.where(has_sign[:, None], shifted, bytes_w)
    dmask = jnp.where(has_sign[:, None], shifted_m, mask)
    return sign, digits, dmask


def parse_int(css, offset, length, width: int = 10) -> Parsed:
    """Fixed-width integer parse (int32).  ``width`` counts digits + sign.

    ``valid`` requires the magnitude to fit int32 (|v| ≤ ``INT32_MAX``):
    values like ``9999999999`` that would silently Horner-wrap instead clear
    ``valid``.  The overflow test runs *before* each Horner step —
    ``acc*10+d > MAX  ⇔  acc > (MAX-d)//10`` — so it never needs a wider
    accumulator.
    """
    raw, mask = gather_field_bytes(css, offset, length, width)
    sign, digits, dmask = _sign_and_digits(raw, mask)
    d = digits.astype(jnp.int32) - _ZERO
    is_digit = (d >= 0) & (d <= 9)
    ok = jnp.all(is_digit | ~dmask, axis=1) & jnp.any(dmask, axis=1)
    ok &= length <= width  # wider fields would truncate silently

    d = jnp.where(dmask, d, 0)
    # Branchless Horner over the fixed width; masked lanes multiply by 1.
    def step(carry, col):
        acc, ovf = carry
        dk, mk = col
        ovf |= mk & (acc > (_I32_MAX - dk) // 10)
        return (acc * jnp.where(mk, 10, 1) + dk, ovf), None

    (acc, ovf), _ = jax.lax.scan(
        step,
        (jnp.zeros(raw.shape[0], jnp.int32), jnp.zeros(raw.shape[0], bool)),
        (d.T, dmask.T),
    )
    ok &= ~ovf
    empty = length == 0
    return Parsed(sign * acc, ok & ~empty, empty)


def parse_int_segmented(css: jax.Array, field_start: jax.Array, field_id: jax.Array,
                        n_fields: int) -> Parsed:
    """Skew-free integer parse over an entire CSS via segmented scan.

    Args:
      css: ``(N,) uint8`` column symbol string (one column's bytes, fields
        back to back — ``tagged`` mode layout).
      field_start: ``(N,) bool`` — True at each field's first byte.
      field_id: ``(N,) int32`` — field index per byte (``n_fields`` = drop).

    The semigroup carries ``(reset, value, ndigits, overflow)``; a reset bit
    makes the scan segmented while staying associative:
        a ⊕ b = b                      if b.reset
                (a.r, a.v·10^min(b.n,9) + b.v, a.n + b.n, a.o|b.o|ovf(a,b))
    Field values are read at each field's *last* byte.

    Overflow is detected exactly (``valid`` clears whenever a field's true
    magnitude exceeds ``INT32_MAX``, matching :func:`parse_int`): while no
    sub-window has overflowed, every carried value is exact, so the combine
    test ``a.v > (MAX - b.v) // 10^b.n`` (or ``a.v > 0`` when ``b`` spans ≥10
    digits) is exact too — and once set, the flag is sticky, which keeps the
    operator associative.  Digit counts are otherwise uncapped: any number of
    leading zeros is fine, which is what removes the old ≤9-digit cap and
    reconciles this path with the ≤10-digit gather parser.
    """
    n = css.shape[0]
    d = css.astype(jnp.int32) - _ZERO
    is_digit = (d >= 0) & (d <= 9)
    is_minus = css == ord("-")
    is_plus = css == ord("+")
    sign_pos = (is_minus | is_plus) & field_start  # sign only legal up front

    elem_v = jnp.where(is_digit, d, 0)
    elem_n = jnp.where(is_digit, 1, 0)
    elem_r = field_start
    elem_o = jnp.zeros(n, bool)

    def op(a, b):
        ar, av, an, ao = a
        br, bv, bn, bo = b
        scale = _POW10_I32[jnp.clip(bn, 0, 9)]
        ovf = jnp.where(bn >= 10, av > 0, av > (_I32_MAX - bv) // scale)
        v = jnp.where(br, bv, av * scale + bv)
        nn = jnp.where(br, bn, an + bn)
        o = jnp.where(br, bo, ao | bo | ovf)
        r = ar | br
        return (r, v, nn, o)

    _, val, ndig, ovf = jax.lax.associative_scan(
        op, (elem_r, elem_v, elem_n, elem_o), axis=0
    )

    # Per-byte validity: digits, or a legal leading sign.
    byte_ok = is_digit | sign_pos
    ok_all = jax.ops.segment_min(
        byte_ok.astype(jnp.int32), field_id, num_segments=n_fields + 1
    )[:-1].astype(bool)

    # Scatter per-field results from each field's last byte.
    pos = jnp.arange(n, dtype=jnp.int32)
    last = jax.ops.segment_max(pos, field_id, num_segments=n_fields + 1)[:-1]
    has_bytes = last >= 0
    last_c = jnp.clip(last, 0)
    value = val[last_c]
    ndigits = ndig[last_c]
    overflowed = ovf[last_c]
    sign = jnp.where(is_minus[jnp.clip(jax.ops.segment_min(pos, field_id, num_segments=n_fields + 1)[:-1], 0)], -1, 1)

    valid = has_bytes & ok_all & (ndigits > 0) & ~overflowed
    return Parsed(sign * value, valid, ~has_bytes)


#: float32 powers of ten, correctly rounded; below 1e-37 float32 is
#: subnormal and flushes to zero, above 1e38 it overflows.
_POW10_MIN, _POW10_MAX = -37, 38


def pow10(exp):
    """``10.0 ** exp`` in float32 for an int32 ``exp``, from a table of
    correctly rounded constants (0 below, inf above its range).

    A select chain rather than ``jnp.power``: the Pallas kernels and XLA
    lower ``pow`` differently on a TPU, and both paths must agree to the
    bit; the table also matches XLA's CPU ``pow`` on every exponent.
    """
    out = jnp.where(exp > _POW10_MAX, jnp.float32(np.inf), jnp.float32(0.0))
    for k in range(_POW10_MIN, _POW10_MAX + 1):
        out = jnp.where(exp == k, jnp.float32(10.0 ** k), out)
    return out


def parse_float(css, offset, length, width: int = 24) -> Parsed:
    """Float32 parse: ``[+-]ddd[.ddd][eE[+-]dd]`` with masked vector passes."""
    raw, mask = gather_field_bytes(css, offset, length, width)
    sign, b, m = _sign_and_digits(raw, mask)
    w = b.shape[1]
    lane = jnp.arange(w, dtype=jnp.int32)[None, :]

    is_dot = (b == ord(".")) & m
    is_e = ((b == ord("e")) | (b == ord("E"))) & m
    dot_pos = jnp.min(jnp.where(is_dot, lane, w), axis=1)   # (R,)
    e_pos = jnp.min(jnp.where(is_e, lane, w), axis=1)

    d = b.astype(jnp.int32) - _ZERO
    is_digit = (d >= 0) & (d <= 9)

    in_mant = m & (lane < e_pos[:, None])
    mant_digit = in_mant & ~is_dot
    # Structural validity: ≤1 dot, dot (if any) before e, mantissa digits are
    # digits, at least one mantissa digit.  dot_pos == w means "no dot" —
    # legal with or without an exponent ("1e+06").
    ok = (jnp.sum(is_dot, axis=1) <= 1) & ((dot_pos <= e_pos) | (dot_pos >= w))
    ok &= jnp.all(is_digit | ~mant_digit, axis=1)
    ok &= jnp.any(mant_digit & is_digit, axis=1)

    dm = jnp.where(mant_digit & is_digit, d, 0)
    active = mant_digit & is_digit

    def mant_step(acc, col):
        dk, ak = col
        return acc * jnp.where(ak, 10.0, 1.0) + dk, None

    macc, _ = jax.lax.scan(
        mant_step, jnp.zeros(b.shape[0], jnp.float32),
        (dm.T.astype(jnp.float32), active.T),
    )
    frac_digits = jnp.sum(active & (lane > dot_pos[:, None]), axis=1)

    # Exponent section.
    after_e = m & (lane > e_pos[:, None])
    e_sign_lane = e_pos + 1
    e_first = jnp.take_along_axis(b, jnp.clip(e_sign_lane, 0, w - 1)[:, None], axis=1)[:, 0]
    has_e = e_pos < w
    e_neg = has_e & (e_first == ord("-"))
    e_signed = has_e & ((e_first == ord("-")) | (e_first == ord("+")))
    exp_digit = after_e & (lane > (e_pos + jnp.where(e_signed, 1, 0))[:, None])
    ok &= jnp.all(is_digit | ~exp_digit, axis=1)
    ok &= ~has_e | jnp.any(exp_digit, axis=1)
    de = jnp.where(exp_digit & is_digit, d, 0)

    def exp_step(acc, col):
        dk, ak = col
        return acc * jnp.where(ak, 10, 1) + dk, None

    eacc, _ = jax.lax.scan(
        exp_step, jnp.zeros(b.shape[0], jnp.int32), (de.T, exp_digit.T)
    )
    exp = jnp.where(e_neg, -eacc, eacc) - frac_digits
    value = sign.astype(jnp.float32) * macc * pow10(exp)

    empty = length == 0
    ok &= length <= width
    return Parsed(value, ok & ~empty, empty)


def _days_in_month(year, mon):
    """Length of ``mon`` in ``year`` (proleptic Gregorian), branchless.

    ``30 + (m + m//8) % 2`` reproduces the 31/30 alternation (with the
    August flip) for every month except February, which gets the leap rule.
    Only meaningful for ``mon`` in 1..12 — callers gate on that separately.
    """
    leap = ((year % 4 == 0) & (year % 100 != 0)) | (year % 400 == 0)
    return jnp.where(mon == 2, 28 + leap.astype(jnp.int32),
                     30 + (mon + mon // 8) % 2)


def _days_from_civil(y, m, d):
    """Howard Hinnant's days-from-civil (proleptic Gregorian → days since epoch)."""
    y = y - (m <= 2)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    mp = (m + 9) % 12
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def parse_date(css, offset, length) -> Parsed:
    """``YYYY-MM-DD[ HH:MM:SS]`` → unix epoch seconds (int32, valid to 2038).

    Validation is semantic, not just structural: the day must exist in the
    (proleptic Gregorian) month — day 31 of a 30-day month and Feb 29 of a
    non-leap year are rejected — and the time section, when present, must
    satisfy ``hh ≤ 23``, ``mm/ss ≤ 59`` with a ``' '`` or ISO-8601 ``'T'``
    date/time separator.
    """
    raw, mask = gather_field_bytes(css, offset, length, 19)
    d = raw.astype(jnp.int32) - _ZERO

    def num(*lanes):
        acc = jnp.zeros(raw.shape[0], jnp.int32)
        for ln in lanes:
            acc = acc * 10 + d[:, ln]
        return acc

    year, mon, day = num(0, 1, 2, 3), num(5, 6), num(8, 9)
    has_time = length >= 19
    hh = jnp.where(has_time, num(11, 12), 0)
    mm = jnp.where(has_time, num(14, 15), 0)
    ss = jnp.where(has_time, num(17, 18), 0)

    digit_lanes = jnp.array([0, 1, 2, 3, 5, 6, 8, 9], jnp.int32)
    time_lanes = jnp.array([11, 12, 14, 15, 17, 18], jnp.int32)
    dd = (d >= 0) & (d <= 9)
    ok = jnp.all(dd[:, digit_lanes], axis=1)
    ok &= (raw[:, 4] == ord("-")) & (raw[:, 7] == ord("-"))
    ok &= (length == 10) | (length == 19)
    time_ok = jnp.all(dd[:, time_lanes], axis=1) & (raw[:, 13] == ord(":")) & (raw[:, 16] == ord(":"))
    time_ok &= (raw[:, 10] == ord(" ")) | (raw[:, 10] == ord("T"))  # ISO 8601 'T' too
    ok &= jnp.where(has_time, time_ok, True)
    ok &= (mon >= 1) & (mon <= 12) & (day >= 1) & (day <= _days_in_month(year, mon))
    ok &= jnp.where(has_time, (hh <= 23) & (mm <= 59) & (ss <= 59), True)

    secs = _days_from_civil(year, mon, day) * 86400 + hh * 3600 + mm * 60 + ss
    empty = length == 0
    return Parsed(secs, ok & ~empty, empty)


def parse_string_noop(css, offset, length) -> Parsed:
    """Strings stay in the CSS; "parsing" is just validity bookkeeping."""
    empty = length == 0
    return Parsed(offset, ~empty, empty)


PARSERS = {
    "int32": parse_int,
    "float32": parse_float,
    "date": parse_date,
    "str": parse_string_noop,
}

# ---------------------------------------------------------------------------
# Type inference (paper §4.3): min numeric type per column via reduction.
# ---------------------------------------------------------------------------

TYPE_CODES = ("int32", "float32", "str")


def infer_column_type(css, offset, length, present, width: int = 24):
    """Returns index into TYPE_CODES: int if every present field parses as
    int, else float if every present field parses as float, else string."""
    live = present & (length > 0)
    ints = parse_int(css, offset, length, width=min(width, 11))
    floats = parse_float(css, offset, length, width=width)
    all_int = jnp.all(ints.valid | ~live)
    all_float = jnp.all(floats.valid | ~live)
    return jnp.where(all_int, 0, jnp.where(all_float, 1, 2))
