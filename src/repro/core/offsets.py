"""Record and column identification (paper §3.2).

Per-symbol record ids are an exclusive prefix sum over the record-delimiter
bitmap.  Column ids need the paper's (abs/rel) semigroup: a chunk that saw a
record delimiter publishes an *absolute* column offset (count of field
delimiters after its last record delimiter), anything else publishes a
*relative* count that accumulates onto its predecessor:

    (a_t, a_o) ⊕ (b_t, b_o) = (b_t, b_o)            if b_t == ABS
                              (a_t, a_o + b_o)       otherwise

Two granularities are implemented:

  * symbol-level, via cumulative sums + a running "last record delimiter"
    cummax — the flattened equivalent used inside a single device, and
  * chunk-level, the paper-faithful summaries combined across chunks.

Both are cross-checked in tests.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import scans
from repro.core.dfa import FIELD_DELIM, RECORD_DELIM

REL = 0
ABS = 1


class SymbolIds(NamedTuple):
    record_id: jax.Array  # (N,) int32 — record each symbol belongs to
    column_id: jax.Array  # (N,) int32 — column each symbol belongs to
    n_records: jax.Array  # () int32


def symbol_ids(classes: jax.Array) -> SymbolIds:
    """Record/column id per symbol from the flattened class stream ``(N,)``.

    Delimiters belong to the field/record they terminate, matching the
    paper's tagging (Fig. 4): a record delimiter's column id is its record's
    last column index.
    """
    classes = classes.reshape(-1)
    n = classes.shape[0]
    is_rec = classes == RECORD_DELIM
    is_fld = classes == FIELD_DELIM

    rec_incl = scans.cumsum(is_rec.astype(jnp.int32))
    record_id = rec_incl - is_rec.astype(jnp.int32)  # exclusive

    # Column = (# field delims strictly before i) − (# field delims at or
    # before the last record delimiter strictly before i).
    idx = jnp.arange(n, dtype=jnp.int32)
    fld_incl = scans.cumsum(is_fld.astype(jnp.int32))
    fld_excl = fld_incl - is_fld.astype(jnp.int32)
    last_rec_incl = scans.cummax(jnp.where(is_rec, idx, -1))
    last_rec_excl = jnp.concatenate([jnp.full((1,), -1, jnp.int32), last_rec_incl[:-1]])
    base = jnp.where(last_rec_excl >= 0, fld_incl[jnp.clip(last_rec_excl, 0)], 0)
    column_id = fld_excl - base
    return SymbolIds(record_id, column_id, rec_incl[-1] if n else jnp.int32(0))


class ChunkSummary(NamedTuple):
    """Per-chunk offset summary (paper Fig. 4, the "abs"/"rel" rows)."""

    rec_count: jax.Array  # (C,) int32 — record delimiters in chunk
    col_tag: jax.Array    # (C,) int32 — ABS iff chunk contains a record delim
    col_off: jax.Array    # (C,) int32 — column offset (absolute or relative)


def chunk_summaries(classes: jax.Array) -> ChunkSummary:
    """Summarise per-chunk class codes ``(C, K)`` into scan elements."""
    is_rec = classes == RECORD_DELIM
    is_fld = classes == FIELD_DELIM
    rec_count = is_rec.sum(axis=1).astype(jnp.int32)
    has_rec = rec_count > 0

    # Zero field-delimiter bits at or before the last record delimiter
    # (paper: "zeroing all bits of the column delimiter bitmap index that
    # precede the last set bit in the record delimiter bitmap index").
    k = classes.shape[1]
    pos = jnp.arange(k, dtype=jnp.int32)
    last_rec = jnp.max(jnp.where(is_rec, pos, -1), axis=1)  # (C,)
    after = pos[None, :] > last_rec[:, None]
    fld_after = (is_fld & after).sum(axis=1).astype(jnp.int32)
    fld_all = is_fld.sum(axis=1).astype(jnp.int32)

    col_tag = jnp.where(has_rec, ABS, REL).astype(jnp.int32)
    col_off = jnp.where(has_rec, fld_after, fld_all)
    return ChunkSummary(rec_count, col_tag, col_off)


def combine_col(a, b):
    """The paper's associative column-offset operator (elementwise batched)."""
    a_t, a_o = a
    b_t, b_o = b
    t = jnp.where(b_t == ABS, b_t, a_t)
    o = jnp.where(b_t == ABS, b_o, a_o + b_o)
    return (t, o)


class ChunkOffsets(NamedTuple):
    rec_offset: jax.Array  # (C,) int32 — records before chunk start
    col_tag: jax.Array     # (C,) int32 — ABS once any predecessor saw a record delim
    col_offset: jax.Array  # (C,) int32 — column index at chunk start


def scan_chunk_offsets(summ: ChunkSummary) -> ChunkOffsets:
    """Exclusive scans giving each chunk its record and column offsets."""
    c = summ.rec_count.shape[0]
    rec_off = scans.cumsum(summ.rec_count) - summ.rec_count

    t_inc, o_inc = jax.lax.associative_scan(
        combine_col, (summ.col_tag, summ.col_off), axis=0
    )
    # Exclusive shift seeded with (REL, 0): the input's first chunk starts at
    # column 0 of record 0.
    zero = jnp.zeros((1,), jnp.int32)
    col_tag = jnp.concatenate([zero + REL, t_inc[:-1]])
    col_off = jnp.concatenate([zero, o_inc[:-1]])
    return ChunkOffsets(rec_off.astype(jnp.int32), col_tag, col_off)


def symbol_ids_from_chunks(
    classes: jax.Array, offs: ChunkOffsets
) -> SymbolIds:
    """Per-symbol ids using chunk offsets (two-level form of ``symbol_ids``).

    ``classes``: ``(C, K)``.  Within each chunk, record/column ids are local
    scans seeded by the chunk's offsets; the column seed only applies until
    the chunk's own first record delimiter (after which ids are chunk-local
    absolutes).
    """
    c, k = classes.shape
    is_rec = classes == RECORD_DELIM
    is_fld = classes == FIELD_DELIM

    rec_local_incl = scans.cumsum(is_rec.astype(jnp.int32), axis=1)
    rec_local_excl = rec_local_incl - is_rec.astype(jnp.int32)
    record_id = offs.rec_offset[:, None] + rec_local_excl

    pos = jnp.arange(k, dtype=jnp.int32)
    fld_incl = scans.cumsum(is_fld.astype(jnp.int32), axis=1)
    fld_excl = fld_incl - is_fld.astype(jnp.int32)
    # Last record delimiter strictly before each position, within the chunk.
    last_rec_incl = scans.cummax(jnp.where(is_rec, pos[None, :], -1), axis=1)
    last_rec_excl = jnp.concatenate(
        [jnp.full((c, 1), -1, jnp.int32), last_rec_incl[:, :-1]], axis=1
    )
    base = jnp.where(
        last_rec_excl >= 0,
        jnp.take_along_axis(fld_incl, jnp.clip(last_rec_excl, 0), axis=1),
        0,
    )
    local_col = fld_excl - base
    # Until the first in-chunk record delimiter, add the chunk's column seed.
    before_first_rec = last_rec_excl < 0
    column_id = jnp.where(before_first_rec, offs.col_offset[:, None] + local_col, local_col)

    n_records = offs.rec_offset[-1] + rec_local_incl[-1, -1] if c else jnp.int32(0)
    return SymbolIds(record_id.reshape(-1), column_id.reshape(-1), n_records)
