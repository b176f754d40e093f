"""Jit'd wrappers for the numparse kernels.

Two families of field-index entry points:

  * ``parse_*_column_fused`` — the default ``backend="pallas"`` path
    (``cfg.fuse_typeconv=True``): hand the CSS plus ``(offset, length)``
    straight to a fused Pallas kernel, which indexes the symbol buffer
    inside the kernel block.  No XLA ``take``/gather and no ``(R, W)``
    row-padded byte matrix between the field index and type conversion.
    By default the fused path is *windowed*: :func:`plan_css_windows`
    derives one contiguous, 128-byte-aligned CSS window per ``window_rows``
    row block (offsets within a column are sorted after the stable
    partition, so a block's fields always share a window), rebases the
    offsets window-relative, and the kernel DMAs only a static
    ``window_bytes`` tile per grid step — VMEM holds ``O(window_bytes)``,
    not the whole CSS, so per-parse input size is no longer capped by
    VMEM capacity.  When the plan detects a window the static tile cannot
    hold (a mega-field longer than the tile) or offsets that are not
    monotone (the sortedness contract violated by a hand-built index),
    the column falls back under ``lax.cond`` to per-row windows (each
    field DMAs its own small aligned window — correct for arbitrary
    offsets), so the windowed path never *compiles* a kernel whose VMEM
    block grows with the CSS, and both branches lower for the TPU.  Same
    arithmetic, same results on every branch.
  * ``parse_*_column``       — the unfused path: gather a column's field
    bytes out of the CSS with XLA's gather and hand the dense ``(R, W)``
    matrix to the arithmetic kernel.  Kept as the ``cfg.fuse_typeconv=False``
    fallback and the benchmark baseline for the fusion.

All share the per-dtype arithmetic (``numparse._*_arith``), so they are
bit-identical.  Row counts that do not divide the kernel block are padded
with zero-length fields and sliced off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import scans
from repro.core import typeconv as typeconv_mod
from repro.core.backends import pad_to_block
from repro.kernels.numparse import numparse

def auto_window_bytes(rows_per_block: int, width: int) -> int:
    """Static CSS window tile (bytes) for ``rows_per_block`` fields.

    Offsets of consecutive fields in one column differ by at most
    ``field length (+1 terminator byte in the inline/vector tagging
    modes)``, so a row block whose fields all fit ``width`` spans at most
    ``rows_per_block * (width + 1) + width`` CSS bytes; one extra
    :data:`numparse.WINDOW_ALIGN` granule absorbs the align-down of the
    window start, and the total rounds up to the alignment.  Fields longer
    than ``width`` (unparseable anyway) may exceed this and take the
    per-row fallback at run time.
    """
    need = rows_per_block * (width + 1) + width + numparse.WINDOW_ALIGN
    a = numparse.WINDOW_ALIGN
    return -(-need // a) * a


def plan_css_windows(offset, length, *, rows_per_block: int, width: int,
                     window_bytes: int, css_len: int):
    """Per-block CSS window plan: ``(win_start, rel_offset, fits)``.

    All jnp (traced, gather-free).  ``offset``/``length`` are ``(R,)`` with
    ``R`` a multiple of ``rows_per_block``.  Empty fields (``length == 0``)
    carry meaningless offsets (the field index emits 0), so each takes the
    running maximum of the non-empty offsets before it — keeping the
    per-block window tight and the effective offsets monotone.  Empties
    *before* the first non-empty field seed from the column's first
    non-empty offset (its minimum, given sortedness) rather than 0, so a
    missing value in record 0 cannot drag an otherwise-tight window back
    to the start of the CSS.

    Returns:
      win_start: ``(R // rows_per_block,) int32`` element offsets into the
        CSS, aligned down to :data:`numparse.WINDOW_ALIGN`.
      rel_offset: ``(R,) int32`` window-relative offsets, clamped to
        ``[0, window_bytes - width]`` (the clamp only matters when ``fits``
        is False and the windowed result is discarded).
      fits: ``() bool`` — True iff every block's fields live inside its
        static ``window_bytes`` tile AND non-empty offsets are monotone
        non-decreasing (the §3.3 sortedness contract).  When False the
        caller must use a fallback path (see ``_fused_column``).
    """
    r = offset.shape[0]
    nb = r // rows_per_block
    nonempty = length > 0
    off_c = jnp.clip(offset.astype(jnp.int32), 0, css_len)
    # Seed for empty rows: the first (= minimum, offsets sorted) non-empty
    # offset, so leading empties inherit forward; css_len if all empty.
    seed = jnp.min(jnp.where(nonempty, off_c, css_len))
    eff = scans.cummax(jnp.where(nonempty, off_c, seed))
    monotone = jnp.all(jnp.where(nonempty, off_c == eff, True))
    eff_blocks = eff.reshape(nb, rows_per_block)
    a = numparse.WINDOW_ALIGN
    win_start = (eff_blocks[:, 0] // a) * a
    need = eff_blocks[:, -1] + width - win_start
    fits = monotone & (jnp.max(need) <= window_bytes)
    start_rep = jnp.broadcast_to(
        win_start[:, None], (nb, rows_per_block)).reshape(-1)
    rel = jnp.clip(jnp.where(nonempty, off_c, eff) - start_rep,
                   0, window_bytes - width)
    return win_start, rel, fits


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def parse_int_fields(field_bytes, lengths,
                     block_rows: int = numparse.DEFAULT_BLOCK_ROWS,
                     *, interpret: bool):
    return numparse.parse_int_fields(
        field_bytes, lengths, block_rows=block_rows, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def parse_float_fields(field_bytes, lengths,
                       block_rows: int = numparse.DEFAULT_BLOCK_ROWS,
                       *, interpret: bool):
    return numparse.parse_float_fields(
        field_bytes, lengths, block_rows=block_rows, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def parse_date_fields(field_bytes, lengths,
                      block_rows: int = numparse.DEFAULT_BLOCK_ROWS,
                      *, interpret: bool):
    return numparse.parse_date_fields(
        field_bytes, lengths, block_rows=block_rows, interpret=interpret
    )


def _gather_and_run(kernel_fn, css, offset, length, width, block_rows, interpret):
    raw, _ = typeconv_mod.gather_field_bytes(css, offset, length, width)
    br = min(block_rows, raw.shape[0])
    padded, n = pad_to_block(raw, br, 0)
    len_p, _ = pad_to_block(length.astype(jnp.int32), br, 0)
    val, ok = kernel_fn(padded, len_p, block_rows=br, interpret=interpret)
    val, ok = val[:n], ok[:n]
    empty = length == 0
    return typeconv_mod.Parsed(val, ok & ~empty, empty)


@functools.partial(jax.jit, static_argnames=("width", "block_rows", "interpret"))
def parse_int_column(css, offset, length, width: int = 11,
                     block_rows: int = numparse.DEFAULT_BLOCK_ROWS,
                     *, interpret: bool) -> typeconv_mod.Parsed:
    """Kernel-backed equivalent of ``typeconv.parse_int``."""
    return _gather_and_run(numparse.parse_int_fields, css, offset, length,
                           width, block_rows, interpret)


@functools.partial(jax.jit, static_argnames=("width", "block_rows", "interpret"))
def parse_float_column(css, offset, length, width: int = 24,
                       block_rows: int = numparse.DEFAULT_BLOCK_ROWS,
                       *, interpret: bool) -> typeconv_mod.Parsed:
    """Kernel-backed equivalent of ``typeconv.parse_float`` (bit-identical)."""
    return _gather_and_run(numparse.parse_float_fields, css, offset, length,
                           width, block_rows, interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def parse_date_column(css, offset, length,
                      block_rows: int = numparse.DEFAULT_BLOCK_ROWS,
                      *, interpret: bool) -> typeconv_mod.Parsed:
    """Kernel-backed equivalent of ``typeconv.parse_date`` (bit-identical)."""
    return _gather_and_run(numparse.parse_date_fields, css, offset, length,
                           numparse.DATE_WIDTH, block_rows, interpret)


# ---------------------------------------------------------------------------
# fused gather+convert entry points (the kernel owns the CSS indexing)
# ---------------------------------------------------------------------------

def _resolve_window(window_rows, window_bytes, width, r):
    """Static window geometry: (rows per window block, window tile bytes)."""
    br = min(window_rows or numparse.DEFAULT_WINDOW_ROWS, r)
    wt = window_bytes or auto_window_bytes(br, width)
    a = numparse.WINDOW_ALIGN
    wt = max(-(-wt // a) * a, -(-(width + a) // a) * a)  # ≥ width + align
    return br, wt


def _plan_column(offset, length, width, window_rows, window_bytes, css_len):
    """Pad a column to whole window blocks and plan its windows:
    ``(br, wt, off_p, len_p, r, win_start, rel, fits)``."""
    br, wt = _resolve_window(window_rows, window_bytes, width, offset.shape[0])
    off_p, r = pad_to_block(offset.astype(jnp.int32), br, 0)
    len_p, _ = pad_to_block(length.astype(jnp.int32), br, 0)
    win_start, rel, fits = plan_css_windows(
        off_p, len_p, rows_per_block=br, width=width, window_bytes=wt,
        css_len=css_len,
    )
    return br, wt, off_p, len_p, r, win_start, rel, fits


def takes_per_row_fallback(css_len: int, offset, length, width: int,
                           window_rows: int = 0, window_bytes: int = 0):
    """``() bool``: whether the fused converter runs this column's
    per-row-window fallback (its windows overflow the static tile, or its
    offsets are not monotone) under the given window geometry."""
    return ~_plan_column(offset, length, width, window_rows, window_bytes,
                         css_len)[-1]


def _windowed_or_per_row(fits, windowed, per_row, operands):
    """``lax.cond(fits, windowed, per_row)`` over ``operands`` that stays a
    branch under ``vmap``.

    ``vmap`` turns a ``cond`` on a batched predicate into a select that runs
    both branches, so every batched partition (the streaming engine and the
    service vmap the whole parse step) would pay for the per-row kernel.
    The batching rule instead runs the windowed kernel on every lane and
    the per-row kernel only when some lane's windows do not fit, keeping
    each lane's own choice.
    """

    @jax.custom_batching.custom_vmap
    def pick(fits, *ops):
        return jax.lax.cond(fits, lambda: windowed(*ops),
                            lambda: per_row(*ops))

    @pick.def_vmap
    def _pick_lanes(axis_size, in_batched, fits, *ops):
        axes = tuple(0 if b else None for b in in_batched[1:])
        lanes_fit = (fits if in_batched[0]
                     else jnp.broadcast_to(fits, (axis_size,)))
        w = jax.vmap(windowed, in_axes=axes, axis_size=axis_size)(*ops)

        def mixed():
            p = jax.vmap(per_row, in_axes=axes, axis_size=axis_size)(*ops)
            return tuple(jnp.where(lanes_fit[:, None], a, b)
                         for a, b in zip(w, p))

        out = jax.lax.cond(jnp.all(lanes_fit), lambda: w, mixed)
        return out, (True, True)

    return pick(fits, *operands)


def _fused_column(windowed_fn, per_row_fn, css, offset, length, width,
                  val_dtype, window_rows, window_bytes, interpret):
    """Shared fused-column body: windowed by default, bounded fallback.

    ``windowed_fn(css, rel, len, win_start, block_rows=, window_bytes=,
    interpret=)`` and ``per_row_fn(css, off, len, interpret=)`` arrive with
    any dtype-specific ``width`` already bound.  The window plan decides at
    run time between the windowed kernel and per-row windows for
    degenerate shapes (mega-fields overflowing the static tile,
    non-monotone offsets): each field then DMAs its own aligned window,
    correct for arbitrary offsets with a bounded VMEM footprint.  Both
    kernels share the arithmetic, so the choice never changes results,
    only footprint and speed.
    """
    r0 = offset.shape[0]
    if r0 == 0:  # degenerate but public: no fields to convert
        zb = jnp.zeros((0,), bool)
        return typeconv_mod.Parsed(jnp.zeros((0,), val_dtype), zb, zb)
    br, wt, off_p, len_p, r, win_start, rel, fits = _plan_column(
        offset, length, width, window_rows, window_bytes, css.shape[0])
    val, ok = _windowed_or_per_row(
        fits,
        lambda c, o, ln, ro, ws: windowed_fn(
            c, ro, ln, ws, block_rows=br, window_bytes=wt,
            interpret=interpret),
        lambda c, o, ln, ro, ws: per_row_fn(c, o, ln, interpret=interpret),
        (css, off_p, len_p, rel, win_start))
    val, ok = val[:r], ok[:r]
    empty = length == 0
    return typeconv_mod.Parsed(val, ok & ~empty, empty)


_FUSED_STATICS = ("width", "window_rows", "window_bytes", "interpret")


@functools.partial(jax.jit, static_argnames=_FUSED_STATICS)
def parse_int_column_fused(css, offset, length, width: int = 11,
                           window_rows: int = 0, window_bytes: int = 0,
                           *, interpret: bool) -> typeconv_mod.Parsed:
    """Fused equivalent of ``parse_int_column`` (bit-identical, no XLA
    gather): per-block CSS window DMA, per-row windows as the fallback."""
    return _fused_column(
        functools.partial(numparse.parse_int_fields_windowed, width=width),
        functools.partial(numparse.parse_int_fields_per_row, width=width),
        css, offset, length, width, jnp.int32, window_rows, window_bytes,
        interpret)


@functools.partial(jax.jit, static_argnames=_FUSED_STATICS)
def parse_float_column_fused(css, offset, length, width: int = 24,
                             window_rows: int = 0, window_bytes: int = 0,
                             *, interpret: bool) -> typeconv_mod.Parsed:
    """Fused equivalent of ``parse_float_column`` (bit-identical, no XLA
    gather): per-block CSS window DMA, per-row windows as the fallback."""
    return _fused_column(
        functools.partial(numparse.parse_float_fields_windowed, width=width),
        functools.partial(numparse.parse_float_fields_per_row, width=width),
        css, offset, length, width, jnp.float32, window_rows, window_bytes,
        interpret)


@functools.partial(jax.jit,
                   static_argnames=("window_rows", "window_bytes", "interpret"))
def parse_date_column_fused(css, offset, length,
                            window_rows: int = 0, window_bytes: int = 0,
                            *, interpret: bool) -> typeconv_mod.Parsed:
    """Fused equivalent of ``parse_date_column`` (bit-identical, no XLA
    gather): per-block CSS window DMA, per-row windows as the fallback."""
    return _fused_column(numparse.parse_date_fields_windowed,
                         numparse.parse_date_fields_per_row,
                         css, offset, length, numparse.DATE_WIDTH, jnp.int32,
                         window_rows, window_bytes, interpret)
