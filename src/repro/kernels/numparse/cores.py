"""Shared per-dtype arithmetic cores for every typed-conversion kernel.

One definition of the §3.3 field arithmetic — int (branchless Horner with
pre-step overflow detection), float (sign/mantissa/dot/exponent sections,
statically unrolled masked Horner), date (digit/separator/civil-calendar
validation + Hinnant days-from-civil) — imported by every conversion
path:

  * the unfused rowwise kernels (``numparse.parse_*_fields``),
  * the windowed-DMA kernels (``parse_*_fields_windowed``) and their
    per-row-window fallback (``parse_*_fields_per_row``),
  * the whole-pipeline megakernel (``kernels/fused_pipeline``).

All run on the VPU with the width axis statically unrolled (W ≤ ~24):
a field's bytes arrive as ``W`` separate arrays, one per byte position,
each shaped like the lengths, so the kernels keep rows on the lanes and no
step slices or reduces across a minor axis.  They only read lanes
``< length`` (or mask them), so every consumer is
bit-identical to the jnp reference (``typeconv.parse_int`` /
``parse_float`` / ``parse_date``) by construction — a single core means
no copy-paste drift between the staged and fused pipelines.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import typeconv as typeconv_mod

DEFAULT_BLOCK_ROWS = 512
#: Gather width for date fields — ``YYYY-MM-DD HH:MM:SS`` is exactly 19 bytes.
DATE_WIDTH = 19
#: Rows per CSS-window block of the windowed kernels: each step's one-hot
#: gather is ``(window bytes, rows)``, so rows × window stays a few
#: hundred KiB of VMEM at every dtype width.
DEFAULT_WINDOW_ROWS = 128
#: CSS window starts are aligned down to this many bytes (the TPU lane
#: count) so the windowed BlockSpec DMA is lane-aligned on real hardware;
#: window tiles are sized in multiples of it.
WINDOW_ALIGN = 128
_ZERO = ord("0")
# Plain Python int: pallas kernels may not capture traced module constants.
_I32_MAX = typeconv_mod.INT32_MAX


def _int_arith(cols, ln):
    """Field bytes as ``W`` int32 arrays ``cols[w]`` (byte ``w`` of every
    field, each shaped like the lengths ``ln``) → ``(value int32, ok bool)``.
    Only lanes ``< ln`` are read."""
    width = len(cols)
    first = cols[0]
    neg = first == ord("-")
    has_sign = neg | (first == ord("+"))
    sign = jnp.where(neg, -1, 1)

    acc = jnp.zeros_like(ln)
    bad = jnp.zeros(ln.shape, jnp.bool_)
    ndig = jnp.zeros_like(ln)
    for w in range(width):
        d = cols[w] - _ZERO
        # lane w is a live digit if it is inside the field and not the sign
        live = (w < ln) & ~(has_sign & (w == 0))
        is_digit = (d >= 0) & (d <= 9)
        bad |= live & ~is_digit
        use = live & is_digit
        # magnitude overflow: acc*10+d would exceed INT32_MAX
        bad |= use & (acc > (_I32_MAX - d) // 10)
        acc = jnp.where(use, acc * 10 + d, acc)
        ndig += use.astype(jnp.int32)

    ok = ~bad & (ndig > 0) & (ln <= width)
    return sign * acc, ok


def _all(masks):
    out = masks[0]
    for m in masks[1:]:
        out = out & m
    return out


def _any(masks):
    out = masks[0]
    for m in masks[1:]:
        out = out | m
    return out


def _count(masks):
    out = jnp.zeros(masks[0].shape, jnp.int32)
    for m in masks:
        out = out + m.astype(jnp.int32)
    return out


def _float_arith(cols, ln):
    """Masked float32 parse of ``W`` byte columns (see :func:`_int_arith`)
    — the same digits, in the same order, through the same float32
    operations as ``typeconv.parse_float``, so the bits agree.

    ``typeconv.parse_float`` shifts the lane window left by one past an
    optional sign; here the sign lane is masked out instead and every
    position is measured in the unshifted window, with ``w_end`` (one past
    the last lane a shifted window could hold) as the "absent" sentinel.
    """
    w = len(cols)
    m = [k < ln for k in range(w)]
    raw = [jnp.where(m[k], cols[k], 0) for k in range(w)]

    first = raw[0]
    has_sign = (first == ord("-")) | (first == ord("+"))
    sign = jnp.where(first == ord("-"), -1, 1).astype(jnp.int32)
    bm = [m[0] & ~has_sign] + m[1:]
    w_end = w + has_sign.astype(jnp.int32)

    is_dot = [(raw[k] == ord(".")) & bm[k] for k in range(w)]
    is_e = [((raw[k] == ord("e")) | (raw[k] == ord("E"))) & bm[k]
            for k in range(w)]
    dot_pos, e_pos = w_end, w_end          # first marker wins
    for k in reversed(range(w)):
        dot_pos = jnp.where(is_dot[k], k, dot_pos)
        e_pos = jnp.where(is_e[k], k, e_pos)

    d = [raw[k] - _ZERO for k in range(w)]
    is_digit = [(d[k] >= 0) & (d[k] <= 9) for k in range(w)]

    mant_digit = [bm[k] & (k < e_pos) & ~is_dot[k] for k in range(w)]
    ok = (_count(is_dot) <= 1) & ((dot_pos <= e_pos) | (dot_pos >= w_end))
    ok &= _all([is_digit[k] | ~mant_digit[k] for k in range(w)])
    active = [mant_digit[k] & is_digit[k] for k in range(w)]
    ok &= _any(active)

    # Mantissa Horner, statically unrolled over the width.
    macc = jnp.zeros(ln.shape, jnp.float32)
    for k in range(w):
        dm = jnp.where(active[k], d[k], 0).astype(jnp.float32)
        macc = jnp.where(active[k], macc * 10.0 + dm, macc)
    frac_digits = _count([active[k] & (k > dot_pos) for k in range(w)])

    # Exponent section.  The lane after the exponent marker is read only
    # when a marker exists; a marker in the last lane has no sign lane.
    e_first = jnp.zeros_like(ln)
    for k in range(w):
        e_first = jnp.where(k == e_pos + 1, raw[k], e_first)
    has_e = e_pos < w_end
    e_neg = has_e & (e_first == ord("-"))
    e_signed = has_e & ((e_first == ord("-")) | (e_first == ord("+")))
    e_lead = e_pos + jnp.where(e_signed, 1, 0)
    exp_digit = [bm[k] & (k > e_lead) for k in range(w)]
    ok &= _all([is_digit[k] | ~exp_digit[k] for k in range(w)])
    ok &= ~has_e | _any(exp_digit)
    eacc = jnp.zeros_like(ln)
    for k in range(w):
        de = jnp.where(exp_digit[k] & is_digit[k], d[k], 0)
        eacc = jnp.where(exp_digit[k], eacc * 10 + de, eacc)

    exp = jnp.where(e_neg, -eacc, eacc) - frac_digits
    value = sign.astype(jnp.float32) * macc * typeconv_mod.pow10(exp)
    ok &= ln <= w
    return value, ok


def _date_arith(cols, ln):
    """``YYYY-MM-DD[ HH:MM:SS]`` over ``DATE_WIDTH`` byte columns — mirrors
    ``typeconv.parse_date`` (civil-calendar + time-range validation)."""
    raw = [jnp.where(k < ln, cols[k], 0) for k in range(DATE_WIDTH)]
    d = [r - _ZERO for r in raw]

    def num(*lanes):
        acc = jnp.zeros_like(ln)
        for k in lanes:
            acc = acc * 10 + d[k]
        return acc

    year, mon, day = num(0, 1, 2, 3), num(5, 6), num(8, 9)
    has_time = ln >= 19
    hh = jnp.where(has_time, num(11, 12), 0)
    mm = jnp.where(has_time, num(14, 15), 0)
    ss = jnp.where(has_time, num(17, 18), 0)

    dd = [(x >= 0) & (x <= 9) for x in d]
    ok = _all([dd[k] for k in (0, 1, 2, 3, 5, 6, 8, 9)])
    ok &= (raw[4] == ord("-")) & (raw[7] == ord("-"))
    ok &= (ln == 10) | (ln == 19)
    time_ok = (_all([dd[k] for k in (11, 12, 14, 15, 17, 18)]) &
               (raw[13] == ord(":")) & (raw[16] == ord(":")) &
               ((raw[10] == ord(" ")) | (raw[10] == ord("T"))))
    ok &= time_ok | ~has_time
    ok &= ((mon >= 1) & (mon <= 12) & (day >= 1) &
           (day <= typeconv_mod._days_in_month(year, mon)))
    ok &= ((hh <= 23) & (mm <= 59) & (ss <= 59)) | ~has_time

    secs = (typeconv_mod._days_from_civil(year, mon, day) * 86400 +
            hh * 3600 + mm * 60 + ss)
    return secs, ok
