"""Format validation and column-count inference (paper §4.3).

ParPaRaw's DFA simulation makes validation nearly free: invalid transitions
are a sink state checked during replay, and the end state must be accepting.
Column-count inference/validation is a segment reduction over per-record
field counts; the paper's chunk-level relative-min/max machinery reappears
in ``chunk_colcount_summary`` for the distributed parser.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import scans
from repro.core.dfa import FIELD_DELIM, RECORD_DELIM, Dfa


class Validation(NamedTuple):
    ok: jax.Array            # () bool — DFA-accepted and no invalid transitions
    end_state_ok: jax.Array  # () bool
    no_invalid: jax.Array    # () bool
    n_records: jax.Array     # () int32
    min_columns: jax.Array   # () int32 — over complete records
    max_columns: jax.Array   # () int32
    record_ok: jax.Array     # (max_records,) bool — per-record conformance


def fields_per_record(
    classes: jax.Array, record_id: jax.Array, max_records: int
) -> jax.Array:
    """Per-record column counts ``(max_records,) int32`` — one more than the
    field delimiters attributed to each record.  Records at or beyond
    ``max_records`` are clipped into a dropped overflow segment.

    Shared between :func:`validate` (single device) and the distributed
    driver, whose shards hold whole records and compute *local* counts on
    shard-local record ids before reducing (``core/distributed.py``).
    """
    classes = classes.reshape(-1)
    is_fld = classes == FIELD_DELIM
    rid = jnp.where(record_id < max_records, record_id, max_records)
    return jax.ops.segment_sum(
        is_fld.astype(jnp.int32), rid, num_segments=max_records + 1
    )[:-1] + 1


def validate(
    classes: jax.Array,
    record_id: jax.Array,
    end_state: jax.Array,
    saw_invalid: jax.Array,
    dfa: Dfa,
    max_records: int,
    expected_columns: int | None = None,
) -> Validation:
    """Global + per-record validation from parse metadata.

    Args:
      classes / record_id: flattened ``(N,)`` streams.
      end_state: final DFA state of the last chunk.
      saw_invalid: ``(n_chunks,) bool`` from replay.
    """
    classes = classes.reshape(-1)
    accept = jnp.asarray(dfa.accept)
    end_ok = accept[end_state.astype(jnp.int32)]
    no_inv = ~jnp.any(saw_invalid)

    is_rec = classes == RECORD_DELIM
    n_records = jnp.sum(is_rec).astype(jnp.int32)

    fields_per_rec = fields_per_record(classes, record_id, max_records)
    rec_live = jnp.arange(max_records) < n_records
    big = jnp.int32(2**31 - 1)
    minc = jnp.min(jnp.where(rec_live, fields_per_rec, big))
    maxc = jnp.max(jnp.where(rec_live, fields_per_rec, 0))

    if expected_columns is None:
        record_ok = rec_live
    else:
        record_ok = rec_live & (fields_per_rec == expected_columns)

    ok = end_ok & no_inv
    if expected_columns is not None:
        ok &= jnp.all(record_ok | ~rec_live)
    return Validation(ok, end_ok, no_inv, n_records, minc, maxc, record_ok)


class ColCountSummary(NamedTuple):
    """Chunk-level column-count bookkeeping (paper §4.3 "relative min/max").

    ``rel`` — field delimiters before the chunk's first record delimiter
    (meaningful only relative to the predecessor's column offset).
    ``minc``/``maxc`` — min/max complete-record column counts observed after
    the first record delimiter; ``has_rec`` gates their validity.
    """

    rel: jax.Array
    minc: jax.Array
    maxc: jax.Array
    has_rec: jax.Array


def chunk_colcount_summary(classes: jax.Array) -> ColCountSummary:
    """Per-chunk summaries over ``(C, K)`` class codes."""
    is_rec = classes == RECORD_DELIM
    is_fld = classes == FIELD_DELIM
    c, k = classes.shape
    pos = jnp.arange(k, dtype=jnp.int32)

    has_rec = jnp.any(is_rec, axis=1)
    first_rec = jnp.min(jnp.where(is_rec, pos[None], k), axis=1)
    rel = jnp.sum(is_fld & (pos[None] < first_rec[:, None]), axis=1).astype(jnp.int32)

    # Complete records inside the chunk: count fields between consecutive
    # in-chunk record delimiters.
    rec_idx = scans.cumsum(is_rec.astype(jnp.int32), axis=1) - is_rec
    fld_per = jax.vmap(
        lambda f, r: jax.ops.segment_sum(f.astype(jnp.int32), r, num_segments=k + 1)
    )(is_fld, jnp.where(is_rec, rec_idx, k))
    # Record r is complete within the chunk iff r >= 1 (its start was the
    # previous in-chunk record delimiter) and r <= last record index.
    n_rec = jnp.sum(is_rec, axis=1)
    ridx = jnp.arange(k + 1, dtype=jnp.int32)
    live = (ridx[None, :] >= 1) & (ridx[None, :] < n_rec[:, None])
    big = jnp.int32(2**31 - 1)
    counts = fld_per + 1
    minc = jnp.min(jnp.where(live, counts, big), axis=1)
    maxc = jnp.max(jnp.where(live, counts, 0), axis=1)
    return ColCountSummary(rel, minc.astype(jnp.int32), maxc.astype(jnp.int32), has_rec)
