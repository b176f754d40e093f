"""Stable partition of symbols by column tag → concatenated symbol strings
(paper §3.3).

The paper uses a stable radix sort over column tags (CUB).  A stable
partition by column tag *is* a stable sort by column tag, and its result is
unique, so every implementation here is bit-identical.  Two forms:

  * ``"sort"`` (``column_layout`` + ``sort_partition``) — one stable
    ``lax.sort`` keyed on the column tag that carries the payloads (symbols,
    record tags, delimiter flags) along, so the sorted key and payloads come
    out directly and no permutation is built, inverted or gathered through
    (keyed on ``col_tag * N + position`` where that fits int32, which is
    unique and needs no stability).
    The column counts come from a dense compare-and-reduce (no
    ``bincount``, which is a scatter-add on a TPU).  ``"auto"`` picks it
    on a TPU, where one sort costs a fraction of the gathers it replaces.
  * gather form (``PARTITION_IMPLS``, plus the Pallas radix kernel in
    ``kernels/partition``) — build the permutation, then ``apply_partition``
    gathers each payload through it.  Column counts in delimiter-separated
    data are tiny (≤ a few dozen), so a single histogram + prefix-sum +
    scatter pass — exactly one radix pass — suffices:

      - ``partition_argsort``  — XLA's stable sort network over the tag key,
        then a gather per payload.
      - ``partition_scatter``  — the paper's radix pass made explicit:
        one-hot histogram, exclusive prefix sum for column starts,
        rank-within-column via a (N × n_cols+1) cumsum, then a scatter that
        inverts the destinations into the permutation.  O(N·C) work, all
        dense vector ops (the reference backend's default).
      - ``partition_scatter2`` — the same pass blocked so ranks fit uint8
        (the default where the Pallas kernels run interpreted).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class Partitioned(NamedTuple):
    perm: jax.Array        # (N,) int32 — destination order (gather indices)
    col_start: jax.Array   # (n_cols+1,) int32 — CSS offset per column
    col_count: jax.Array   # (n_cols+1,) int32 — symbols per column
    # (the sentinel "drop" partition is the trailing entry of both)


def column_histogram(col_tag: jax.Array, n_cols: int) -> jax.Array:
    """Counts per column including the sentinel drop column: ``(n_cols+1,)``."""
    return jnp.bincount(col_tag, length=n_cols + 1).astype(jnp.int32)


def partition_argsort(col_tag: jax.Array, n_cols: int) -> Partitioned:
    perm = jnp.argsort(col_tag, stable=True).astype(jnp.int32)
    count = column_histogram(col_tag, n_cols)
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(count)[:-1]])
    return Partitioned(perm, start, count)


def partition_scatter(col_tag: jax.Array, n_cols: int) -> Partitioned:
    """Single stable radix pass: histogram → exclusive scan → rank → scatter.

    ``perm`` is returned in gather form (like argsort) so the two paths are
    drop-in interchangeable; the scatter computes destination positions and
    inverts them.
    """
    n = col_tag.shape[0]
    cols = jnp.arange(n_cols + 1, dtype=jnp.int32)
    onehot = (col_tag[:, None] == cols[None, :]).astype(jnp.int32)  # (N, C+1)
    count = onehot.sum(axis=0).astype(jnp.int32)
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(count)[:-1]])
    # Rank of each symbol within its own column (stable: input order).
    ranks = jnp.cumsum(onehot, axis=0) - onehot
    own_rank = jnp.take_along_axis(ranks, col_tag[:, None], axis=1)[:, 0]
    dest = start[col_tag] + own_rank  # (N,) — a permutation of [0, N)
    # Invert: perm[dest[i]] = i, giving gather indices.
    perm = jnp.zeros((n,), jnp.int32).at[dest].set(jnp.arange(n, dtype=jnp.int32))
    return Partitioned(perm, start, count)


def partition_scatter2(col_tag: jax.Array, n_cols: int,
                       block: int = 128) -> Partitioned:
    """Two-level counting scatter — the classic GPU radix-pass structure
    (per-block histogram → inter-block scan → intra-block ranks) re-tiled
    for HBM traffic instead of shared memory.

    The flat pass's dominant cost is the (N × C) int32 one-hot cumsum
    (~12·N·C bytes of traffic).  Blocking bounds intra-block ranks by
    ``block`` ≤ 255 so they fit uint8 (~2·N·C bytes), and the inter-block
    scan shrinks to (N/block × C) int32 — a ~6× traffic cut on the
    partition step.
    """
    n = col_tag.shape[0]
    assert block < 256, "intra-block ranks must fit uint8"
    nb = -(-n // block)
    pad = nb * block - n
    tags = jnp.concatenate(
        [col_tag, jnp.full((pad,), n_cols, col_tag.dtype)]) if pad else col_tag
    tags2 = tags.reshape(nb, block)
    cols = jnp.arange(n_cols + 1, dtype=jnp.int32)
    onehot8 = (tags2[:, :, None] == cols[None, None, :]).astype(jnp.uint8)

    # per-block histograms + intra-block exclusive ranks (uint8 traffic)
    block_hist = onehot8.sum(axis=1, dtype=jnp.int32)          # (NB, C+1)
    ranks8 = jnp.cumsum(onehot8, axis=1, dtype=jnp.uint8)      # inclusive
    own_rank = jnp.take_along_axis(
        ranks8, tags2[:, :, None].astype(jnp.int32), axis=2
    )[:, :, 0].astype(jnp.int32) - 1                           # exclusive

    # inter-block exclusive scan per column (tiny: N/block × C)
    blk_excl = jnp.cumsum(block_hist, axis=0) - block_hist     # (NB, C+1)
    count = block_hist.sum(axis=0)
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(count)[:-1]])
    # padding rows land in the sentinel column and past position n; they are
    # sliced off dest below but must not inflate the reported count
    count = count.at[-1].add(-pad)

    base = start[tags2] + jnp.take_along_axis(
        blk_excl, tags2.astype(jnp.int32), axis=1)
    dest = (base + own_rank).reshape(-1)[:n]
    perm = jnp.zeros((n,), jnp.int32).at[dest].set(jnp.arange(n, dtype=jnp.int32))
    return Partitioned(perm, start, count)


def apply_partition(perm: jax.Array, *arrays: jax.Array):
    """Gather any number of parallel payload arrays through ``perm``."""
    out = tuple(a.reshape(-1)[perm] for a in arrays)
    return out if len(out) != 1 else out[0]


def column_layout(col_tag: jax.Array, n_cols: int):
    """``(col_start, col_count)``, each ``(n_cols+1,)`` int32 with the
    sentinel drop column last, for ``partition_impl="sort"``: counts by a
    dense compare of every tag against every column and a reduce."""
    cols = jnp.arange(n_cols + 1, dtype=jnp.int32)
    count = (col_tag[None, :] == cols[:, None]).sum(axis=1, dtype=jnp.int32)
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(count)[:-1]])
    return start, count


def sort_partition(col_tag: jax.Array, n_cols: int, *payloads: jax.Array):
    """``partition_impl="sort"``: one stable sort keyed on ``col_tag`` that
    carries ``payloads`` along.  Returns ``(col_sorted, *payloads_sorted)``,
    exactly what ``apply_partition`` gives for the same tags under any
    gather-form implementation.

    Where every ``col_tag * n + position`` fits int32 (decided from the
    static shape), that unique key sorts unstably to the same order and
    spares the sort the position operand XLA adds for stability."""
    n = col_tag.shape[0]
    payloads = tuple(a.reshape(-1) for a in payloads)
    if 0 < n and (n_cols + 1) * n < 2**31:
        key = col_tag * n + jnp.arange(n, dtype=jnp.int32)
        key, *out = jax.lax.sort((key, *payloads), num_keys=1,
                                 is_stable=False)
        return (key // n, *out)
    return tuple(jax.lax.sort((col_tag, *payloads), num_keys=1,
                              is_stable=True))


PARTITION_IMPLS = {
    "argsort": partition_argsort,
    "scatter": partition_scatter,
    "scatter2": partition_scatter2,
}
