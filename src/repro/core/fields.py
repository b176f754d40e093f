"""CSS field index: per-field offsets and lengths (paper §3.3, Fig. 5).

The paper run-length-encodes record tags inside each column's CSS and
prefix-sums the run lengths.  On TPU the same index falls out of two
segment reductions keyed by (column, record):

    offset[c, r] = min position of a (c, r) symbol
    length[c, r] = count of (c, r) symbols

which additionally handles *empty* fields (no symbols at all → length 0,
offset patched harmlessly) and *missing* fields in ragged records, neither
of which produce an RLE run.  For the inline/vector tagging modes the index
instead derives from terminator/flag positions, matching paper §4.1.

:func:`field_index` dispatches on the tagging mode — the single entry point
``stages.materialize`` uses, so the mode split lives here with the index
logic rather than in the stage layer.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import scans

_BIG = jnp.int32(2**31 - 1)


class FieldIndex(NamedTuple):
    offset: jax.Array   # (n_cols, max_records) int32 — absolute into the CSS buffer
    length: jax.Array   # (n_cols, max_records) int32
    present: jax.Array  # (n_cols, max_records) bool — field materialised in input


def field_index(
    mode: str,
    col_sorted: jax.Array,
    rec_sorted: jax.Array,
    col_start: jax.Array,
    n_cols: int,
    max_records: int,
    term_flag=None,
) -> "FieldIndex":
    """Build the field index for a tagging mode (paper §3.3 / §4.1).

    ``tagged`` derives the index from the sorted (column, record) tags and
    ignores ``col_start``/``term_flag``; ``inline``/``vector`` derive it
    from the partitioned terminator flags (``term_flag`` required).
    """
    if mode == "tagged":
        return field_index_tagged(col_sorted, rec_sorted, n_cols, max_records)
    if term_flag is None:
        raise ValueError(f"tagging mode {mode!r} needs the terminator flags")
    return field_index_terminated(
        term_flag, col_sorted, rec_sorted, col_start, n_cols, max_records
    )


def field_index_tagged(
    col_sorted: jax.Array,
    rec_sorted: jax.Array,
    n_cols: int,
    max_records: int,
) -> FieldIndex:
    """Index from sorted (column, record) tags — ``tagged`` mode.

    Args:
      col_sorted / rec_sorted: ``(N,) int32`` tags after partitioning (value
        symbols grouped by column, original order preserved within).
    """
    n = col_sorted.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    in_range = (col_sorted < n_cols) & (rec_sorted < max_records)
    n_segs = n_cols * max_records
    seg = jnp.where(in_range, col_sorted * max_records + rec_sorted, n_segs)

    offset = jax.ops.segment_min(pos, seg, num_segments=n_segs + 1)[:-1]
    length = jax.ops.segment_sum(
        jnp.ones_like(pos), seg, num_segments=n_segs + 1
    )[:-1]
    present = length > 0
    offset = jnp.where(present, offset, 0)
    return FieldIndex(
        offset.reshape(n_cols, max_records).astype(jnp.int32),
        length.reshape(n_cols, max_records).astype(jnp.int32),
        present.reshape(n_cols, max_records),
    )


def field_index_terminated(
    term_flag_sorted: jax.Array,
    col_sorted: jax.Array,
    rec_sorted: jax.Array,
    col_start: jax.Array,
    n_cols: int,
    max_records: int,
) -> FieldIndex:
    """Index from terminator positions — ``inline``/``vector`` modes.

    Each terminator carries the (column, record) of the field it closes, so
    a segment-min keyed on those tags lands every field's *end*; the start is
    the previous field's end + 1 (one terminator byte separates fields), and
    the column's CSS start for the first record.

    Args:
      term_flag_sorted: ``(N,) bool`` terminator marker after partitioning.
      col_start: ``(≥n_cols,) int32`` CSS start per column (from the
        partition histogram).
    """
    n = term_flag_sorted.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    in_range = (col_sorted < n_cols) & (rec_sorted < max_records)
    valid = term_flag_sorted & in_range
    n_segs = n_cols * max_records
    seg = jnp.where(valid, col_sorted * max_records + rec_sorted, n_segs)

    end = jax.ops.segment_min(
        jnp.where(valid, pos, _BIG), seg, num_segments=n_segs + 1
    )[:-1].reshape(n_cols, max_records)
    present = end < _BIG

    # Start = one past the previous *present* field's terminator (absent
    # fields — a record whose field terminated on an earlier shard, or a
    # ragged record's missing column — contribute no bytes), or the
    # column's CSS start when no field precedes.  Ends are monotone within
    # a column (stable partition), so an exclusive running max finds the
    # predecessor; with every field present this reduces to the plain
    # ``end[r-1] + 1`` recurrence bit-for-bit.
    prev_end = scans.cummax(jnp.where(present, end, -1), axis=1)
    prev_end = jnp.concatenate(
        [jnp.full((n_cols, 1), -1, end.dtype), prev_end[:, :-1]], axis=1
    )
    start = jnp.where(prev_end >= 0, prev_end + 1, col_start[:n_cols, None])
    length = jnp.where(present, end - start, 0)
    offset = jnp.where(present, start, 0)
    return FieldIndex(
        offset.astype(jnp.int32), length.astype(jnp.int32), present
    )
