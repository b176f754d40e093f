"""Stable partition of symbols by column tag → concatenated symbol strings
(paper §3.3).

The paper uses a stable radix sort over column tags (CUB).  Column counts in
delimiter-separated data are tiny (≤ a few dozen), so a single
histogram + prefix-sum + scatter pass — exactly one radix pass — suffices.
Two TPU-friendly implementations:

  * ``partition_argsort``  — XLA's stable sort network over the tag key.
    O(N log² N) comparator depth but a single fused op; the robust default.
  * ``partition_scatter``  — the paper's radix pass made explicit: one-hot
    histogram, exclusive prefix sum for column starts, rank-within-column via
    a (N × n_cols+1) cumsum, then a scatter.  O(N·C) work, all dense vector
    ops; wins for small C (§Perf measures the crossover).

Both return the permutation so callers can carry any payload (symbols,
record tags, delimiter flags) through the same reordering.
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


PARTITION_IMPLS = {
    "argsort": partition_argsort,
    "scatter": partition_scatter,
    "scatter2": partition_scatter2,
}
