"""Pallas TPU kernels for typed field conversion (paper §3.3 type conversion).

Three kernel families share one arithmetic core per dtype
(``cores._int_arith`` / ``cores._float_arith`` / ``cores._date_arith`` —
all on the VPU, width axis statically unrolled, W ≤ ~24; the
whole-pipeline megakernel in ``kernels/fused_pipeline`` imports the same
cores):

  * ``parse_*_fields``        — unfused: the caller gathers each field's
    bytes out of the CSS with XLA's gather and hands the kernel a dense
    ``(R, W)`` byte matrix.  One grid step processes ``block_rows`` fields.
  * ``parse_*_fields_windowed`` — the fused default: the kernel receives
    the CSS itself plus ``(offset, length)`` and reads each field's bytes
    in-kernel.  Offsets within a column are sorted after the stable
    partition, so each ``block_rows`` row block's fields live in ONE
    contiguous CSS window.  The op layer
    (``ops.plan_css_windows``) precomputes a 128-byte-aligned
    ``window_start`` per grid step plus window-relative offsets, and a
    scalar-prefetched ``pl.Element`` BlockSpec DMAs only that static
    ``window_bytes`` tile into VMEM per step; the kernel reads each
    field's bytes out of the window with one MXU matmul
    (:func:`_window_gather`).  VMEM footprint is ``O(window_bytes)``
    regardless of CSS size — the same locality trick GPU decompressors use
    for coalesced access (Sitaridi et al., arXiv:1606.00519).
  * ``parse_*_fields_per_row`` — the windowed family's fallback for
    degenerate shapes (a mega-field stretching a window past its static
    tile, or non-monotone offsets that violate the sortedness contract),
    chosen per column at run time via ``lax.cond`` (``ops._fused_column``):
    every field DMAs its own small aligned window from the CSS in HBM, so
    arbitrary offsets parse correctly, and no compiled kernel's VMEM block
    grows with the CSS.
    ``fuse_typeconv=False`` remains the escape hatch that avoids fused
    CSS indexing entirely.

Because every family runs the same arithmetic on the same live lanes, they
are bit-identical to each other and to the jnp reference
(``typeconv.parse_int`` / ``parse_float`` / ``parse_date``).  Dead lanes
(beyond ``length``) may differ between families — the unfused gather
pre-masks them to 0, the fused paths read whatever follows the field — but
every dtype's arithmetic either masks on ``lane < length`` itself or never
consumes dead lanes.

Kernels cover every non-string dtype the schema layer knows:

  * int   — sign detection, digit validation, branchless Horner with
    pre-step overflow detection (``acc*10+d > MAX ⇔ acc > (MAX-d)//10`` —
    no wider accumulator needed).
  * float — sign/mantissa/dot/exponent sections with statically-unrolled
    masked Horner, mirroring ``typeconv.parse_float`` op-for-op.
  * date  — per-lane digit/separator validation (including days-in-month
    and time-range semantics) + Hinnant days-from-civil, mirroring
    ``typeconv.parse_date``.

This is the thread-exclusive collaboration level of the paper; the skew-
robust fallback (segmented-scan Horner over the raw CSS) lives in
``repro.core.typeconv.parse_int_segmented``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Shared per-dtype arithmetic + constants live in ``cores`` (one definition
# for the kernel families here AND the whole-pipeline megakernel);
# re-exported under the historical names for callers that import them from
# this module.
from repro.kernels.numparse.cores import (  # noqa: F401
    DATE_WIDTH,
    DEFAULT_BLOCK_ROWS,
    DEFAULT_WINDOW_ROWS,
    WINDOW_ALIGN,
    _date_arith,
    _float_arith,
    _int_arith,
)


def _rows(ref):
    """A ``(1, BR)`` row block as int32."""
    return ref[...].astype(jnp.int32)


def _store(val_ref, ok_ref, val, ok):
    val_ref[...] = val
    ok_ref[...] = ok.astype(jnp.int32)


def _make_rowwise_kernel(arith):
    """Unfused kernel: the ``(W, BR)`` block holds the pre-gathered bytes
    of ``BR`` fields, byte position on the sublanes, fields on the lanes."""

    def kernel(bytes_ref, len_ref, val_ref, ok_ref):
        b = bytes_ref[...].astype(jnp.int32)       # (W, BR)
        cols = [b[w:w + 1, :] for w in range(b.shape[0])]
        _store(val_ref, ok_ref, *arith(cols, _rows(len_ref)))

    return kernel


# ---------------------------------------------------------------------------
# fused gather+convert kernels: index the CSS inside the kernel
# ---------------------------------------------------------------------------

def _window_gather(win, rel, width: int):
    """``cols[w][0, r] = win[0, rel[0, r] + w]`` for the ``(1, BR)`` row of
    window-relative offsets ``rel`` (pre-clamped to ``[0, WT - width]``).

    Mosaic lowers no general gather, so the window is read with one MXU
    matmul: the ``(W, WT)`` stack of the window shifted left by
    ``0 … W-1`` lanes against the ``(WT, BR)`` one-hot of each field's
    offset.  Bytes and 0/1 are exact in bfloat16 and each output sums
    exactly one product, so the result is the bytes themselves.
    """
    wt = win.shape[1]
    wf = win.astype(jnp.int32).astype(jnp.float32)            # (1, WT)
    sub = jax.lax.broadcasted_iota(jnp.int32, (width, wt), 0)
    shifted = jnp.zeros((width, wt), jnp.float32)
    for w in range(width):
        # row w holds win[j + w] at lane j (lanes past WT - w wrap, unread)
        row = wf if w == 0 else pltpu.roll(wf, wt - w, 1)
        shifted = jnp.where(sub == w, row, shifted)
    pos = jax.lax.broadcasted_iota(jnp.int32, (wt, rel.shape[1]), 0)
    onehot = (pos == rel).astype(jnp.bfloat16)                # (WT, BR)
    b = jnp.dot(shifted.astype(jnp.bfloat16), onehot,
                preferred_element_type=jnp.float32).astype(jnp.int32)
    return [b[w:w + 1, :] for w in range(width)]


def _make_windowed_kernel(arith, width: int):
    """Wrap a per-dtype arithmetic in the windowed in-kernel CSS gather.

    The first input ref holds only this grid step's ``(1, window_bytes)``
    CSS window (selected by the scalar-prefetched ``pl.Element`` BlockSpec) and the offsets arrive
    window-relative, pre-clamped by the op layer to ``[0, WT - width]`` so
    ``rel + w`` never leaves the tile.
    """

    def kernel(win_start_ref, win_ref, off_ref, len_ref, val_ref, ok_ref):
        del win_start_ref  # consumed by the BlockSpec index_map only
        cols = _window_gather(win_ref[...], _rows(off_ref), width)
        _store(val_ref, ok_ref, *arith(cols, _rows(len_ref)))

    return kernel


#: Per-row window tile: a 1-D uint8 CSS in HBM is tiled in 1024-byte
#: granules, which a DMA must start on and cover whole, so each field
#: copies the two granules around its start — any field width up to
#: ``WINDOW_ALIGN`` bytes fits.
_PER_ROW_GRANULE = 1024
PER_ROW_WINDOW = 2 * _PER_ROW_GRANULE
#: Fields per grid step of the per-row-window kernels.
PER_ROW_BLOCK_ROWS = 1024


def _make_per_row_kernel(arith, block_rows: int, width: int):
    """Per-row windows: each field DMAs its own aligned
    ``PER_ROW_WINDOW``-byte CSS window, so the kernel is correct for
    arbitrary offsets (unsorted, or around mega-fields) with an
    ``O(block_rows · PER_ROW_WINDOW)`` VMEM footprint.

    The CSS stays in HBM; the step's absolute offsets arrive in SMEM.
    All ``block_rows`` window copies are started, then all are waited on,
    before any window is read: they share one DMA semaphore, which counts
    landed bytes, not which copy landed them, so only the last wait proves
    every window is in VMEM.  Each window is then rotated so its field
    starts at lane 0 and its first ``WINDOW_ALIGN`` bytes are parked as one
    row of an int32 scratch, whose transpose gives the per-byte-position
    rows the arithmetic reads.
    """
    g, wt = _PER_ROW_GRANULE, PER_ROW_WINDOW

    def kernel(off_ref, css_ref, len_ref, val_ref, ok_ref, wins, rows, sem):
        def copy(r, start):
            return pltpu.make_async_copy(
                css_ref.at[pl.ds(start, wt)], wins.at[pl.ds(r * wt, wt)], sem)

        def offset(r):
            return off_ref[r // WINDOW_ALIGN, r % WINDOW_ALIGN]

        def start(r, c):
            copy(r, pl.multiple_of(offset(r) // g * g, g)).start()
            return c

        def land(r, c):
            copy(r, 0).wait()           # same size as every copy started
            return c

        def park(r, c):
            row = wins[pl.ds(pl.multiple_of(r * wt, wt), wt)]
            row = row.astype(jnp.int32)[None, :]             # (1, WT)
            row = pltpu.roll(row, wt - offset(r) % g, 1)     # field at lane 0
            rows[pl.ds(r, 1), :] = row[:, :WINDOW_ALIGN]
            return c

        jax.lax.fori_loop(0, block_rows, start, 0)
        jax.lax.fori_loop(0, block_rows, land, 0)
        jax.lax.fori_loop(0, block_rows, park, 0)
        b = rows[...].T                                      # (A, BR)
        cols = [b[w:w + 1, :] for w in range(width)]
        _store(val_ref, ok_ref, *arith(cols, _rows(len_ref)))

    return kernel


# ---------------------------------------------------------------------------
# pallas_call plumbing (shared by all kernels)
#
# Per-field vectors (offsets, lengths, values, ok flags) travel as (1, R)
# rows: fields ride the lanes, so no (R, 1) column is ever padded out to a
# full lane tile in HBM.
# ---------------------------------------------------------------------------

def _row(x):
    return x.astype(jnp.int32)[None, :]


def _row_outputs(r, br, val_dtype, index_map):
    specs = [pl.BlockSpec((1, br), index_map)] * 2
    shapes = [jax.ShapeDtypeStruct((1, r), val_dtype),
              jax.ShapeDtypeStruct((1, r), jnp.int32)]
    return specs, shapes


def _call_rowwise(arith, field_bytes, lengths, block_rows, val_dtype,
                  interpret):
    r, w = field_bytes.shape
    br = min(block_rows, r)
    if r % br:
        raise ValueError(f"rows {r} not a multiple of block_rows {br}")
    out_specs, out_shape = _row_outputs(r, br, val_dtype, lambda i: (0, i))
    val, ok = pl.pallas_call(
        _make_rowwise_kernel(arith),
        grid=(r // br,),
        in_specs=[
            pl.BlockSpec((w, br), lambda i: (0, i)),
            pl.BlockSpec((1, br), lambda i: (0, i)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(field_bytes.T, _row(lengths))
    return val[0], ok[0].astype(bool)


def _windowed_call(arith, css, rel_off, lengths, win_start, width, block_rows,
                   window_bytes, val_dtype, interpret):
    """Run a windowed kernel over pre-planned windows.

    ``rel_off``/``lengths`` are ``(R,)`` with ``R`` a multiple of
    ``block_rows``; ``win_start`` is ``(R // block_rows,)`` element offsets
    (multiples of :data:`WINDOW_ALIGN`) from :func:`ops.plan_css_windows`.
    The CSS is tile-padded so every ``win_start + window_bytes`` slice is in
    range; each grid step DMAs exactly one ``(1, window_bytes)`` tile.
    """
    r = rel_off.shape[0]
    br = block_rows
    if r % br:
        raise ValueError(f"rows {r} not a multiple of block_rows {br}")
    css_p = jnp.concatenate([css, jnp.zeros((window_bytes,), css.dtype)])[None, :]
    out_specs, out_shape = _row_outputs(r, br, val_dtype,
                                        lambda i, ws: (0, i))
    val, ok = pl.pallas_call(
        _make_windowed_kernel(arith, width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r // br,),
            in_specs=[
                # element-offset window: start = win_start[i], passed in
                # WINDOW_ALIGN units so Mosaic can prove the DMA aligned
                pl.BlockSpec((pl.Element(1), pl.Element(window_bytes)),
                             lambda i, ws: (0, ws[i] * WINDOW_ALIGN)),
                pl.BlockSpec((1, br), lambda i, ws: (0, i)),
                pl.BlockSpec((1, br), lambda i, ws: (0, i)),
            ],
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(win_start.astype(jnp.int32) // WINDOW_ALIGN, css_p, _row(rel_off),
      _row(lengths))
    return val[0], ok[0].astype(bool)


def _per_row_call(arith, css, offsets, lengths, width, val_dtype, interpret):
    """Run a per-row-window kernel; ``offsets`` are absolute CSS offsets.

    Rows go ``PER_ROW_BLOCK_ROWS`` to a step; the offsets reach SMEM as
    ``(8, 128)`` blocks of a ``(R / 128, 128)`` array, the tiling Mosaic
    accepts for SMEM blocks (also under ``vmap``).  The tail is padded
    with empty fields and sliced off.
    """
    n = css.shape[0]
    r = offsets.shape[0]
    if width > WINDOW_ALIGN:
        raise ValueError(f"per-row windows hold fields up to {WINDOW_ALIGN} "
                         f"bytes, got width {width}")
    br = PER_ROW_BLOCK_ROWS
    pad = -r % br
    offs = jnp.pad(jnp.clip(offsets.astype(jnp.int32), 0, n), (0, pad))
    lens = jnp.pad(lengths.astype(jnp.int32), (0, pad))
    css_p = jnp.concatenate([css, jnp.zeros((PER_ROW_WINDOW,), css.dtype)])
    out_specs, out_shape = _row_outputs(r + pad, br, val_dtype,
                                        lambda i: (0, i))
    call = pl.pallas_call(
        _make_per_row_kernel(arith, br, width),
        grid=((r + pad) // br,),
        in_specs=[
            pl.BlockSpec((br // WINDOW_ALIGN, WINDOW_ALIGN), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec((1, br), lambda i: (0, i)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((br * PER_ROW_WINDOW,), jnp.uint8),
            pltpu.VMEM((br, WINDOW_ALIGN), jnp.int32),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=interpret,
    )

    # Pallas batches a kernel by adding a grid axis, which an HBM-resident
    # operand cannot follow; batched callers (the streaming engine vmaps
    # the whole parse step) run one unbatched kernel per lane instead.
    @jax.custom_batching.custom_vmap
    def run(offs2d, css_b, lens_row):
        return call(offs2d, css_b, lens_row)

    @run.def_vmap
    def _run_lanes(axis_size, in_batched, *args):
        args = tuple(a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                     for a, b in zip(args, in_batched))
        return jax.lax.map(lambda a: run(*a), args), (True, True)

    val, ok = run(offs.reshape(-1, WINDOW_ALIGN), css_p, lens[None, :])
    return val[0, :r], ok[0, :r].astype(bool)


def parse_int_fields(
    field_bytes: jax.Array,
    lengths: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool,
):
    """``(R, W) uint8`` gathered field bytes + ``(R,) int32`` lengths →
    ``(value (R,) int32, ok (R,) bool)``."""
    return _call_rowwise(_int_arith, field_bytes, lengths, block_rows,
                         jnp.int32, interpret)


def parse_float_fields(
    field_bytes: jax.Array,
    lengths: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool,
):
    """``(R, W) uint8`` gathered field bytes + ``(R,) int32`` lengths →
    ``(value (R,) float32, ok (R,) bool)`` — bit-identical to
    ``typeconv.parse_float`` on every field."""
    return _call_rowwise(_float_arith, field_bytes, lengths, block_rows,
                         jnp.float32, interpret)


def parse_date_fields(
    field_bytes: jax.Array,
    lengths: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool,
):
    """``(R, 19) uint8`` gathered field bytes + ``(R,) int32`` lengths →
    ``(epoch_secs (R,) int32, ok (R,) bool)`` — bit-identical to
    ``typeconv.parse_date`` on every field."""
    w = field_bytes.shape[1]
    if w != DATE_WIDTH:
        raise ValueError(f"date fields must be gathered at width {DATE_WIDTH}, got {w}")
    return _call_rowwise(_date_arith, field_bytes, lengths, block_rows,
                         jnp.int32, interpret)


def parse_int_fields_windowed(
    css: jax.Array,
    rel_offsets: jax.Array,
    lengths: jax.Array,
    win_start: jax.Array,
    *,
    width: int,
    block_rows: int,
    window_bytes: int,
    interpret: bool,
):
    """``(N,) uint8`` CSS + ``(R,) int32`` window-relative offsets/lengths
    + per-block window starts → ``(value (R,) int32, ok (R,) bool)``, the
    gather inside the kernel (see ``ops.plan_css_windows``)."""
    return _windowed_call(_int_arith, css, rel_offsets, lengths, win_start, width,
                          block_rows, window_bytes, jnp.int32, interpret)


def parse_float_fields_windowed(
    css: jax.Array,
    rel_offsets: jax.Array,
    lengths: jax.Array,
    win_start: jax.Array,
    *,
    width: int,
    block_rows: int,
    window_bytes: int,
    interpret: bool,
):
    """Windowed float32 twin of ``parse_float_fields`` — bit-identical."""
    return _windowed_call(_float_arith, css, rel_offsets, lengths, win_start, width,
                          block_rows, window_bytes, jnp.float32, interpret)


def parse_date_fields_windowed(
    css: jax.Array,
    rel_offsets: jax.Array,
    lengths: jax.Array,
    win_start: jax.Array,
    *,
    block_rows: int,
    window_bytes: int,
    interpret: bool,
):
    """Windowed date twin of ``parse_date_fields`` — bit-identical."""
    return _windowed_call(_date_arith, css, rel_offsets, lengths, win_start,
                          DATE_WIDTH, block_rows, window_bytes, jnp.int32,
                          interpret)


def parse_int_fields_per_row(css, offsets, lengths, *, width: int,
                             interpret: bool):
    """Per-row-window twin of ``parse_int_fields_windowed`` for arbitrary
    (unsorted, mega-field) offsets — bit-identical."""
    return _per_row_call(_int_arith, css, offsets, lengths, width,
                         jnp.int32, interpret)


def parse_float_fields_per_row(css, offsets, lengths, *, width: int,
                               interpret: bool):
    """Per-row-window twin of ``parse_float_fields_windowed``."""
    return _per_row_call(_float_arith, css, offsets, lengths, width,
                         jnp.float32, interpret)


def parse_date_fields_per_row(css, offsets, lengths, *, interpret: bool):
    """Per-row-window twin of ``parse_date_fields_windowed``."""
    return _per_row_call(_date_arith, css, offsets, lengths, DATE_WIDTH,
                         jnp.int32, interpret)
