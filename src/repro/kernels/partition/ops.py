"""Jit'd wrapper for the partition kernel: the §3.3 stable partition.

``partition_tags`` is the ``ParseBackend.partition`` entry point for
``backend="pallas"`` (``partition_impl="kernel"``): pad the tag stream to
a whole number of blocks with the sentinel column, run the single-pass
Pallas radix kernel (per-block histograms + running carry + intra-block
ranks → column-relative destinations), lift the relative destinations to
global ones with the tiny ``(n_cols+1,)`` exclusive prefix, and invert the
destination map into the gather-form permutation every ``partition_impl``
returns — so the kernel path is drop-in interchangeable with the jnp impls
and bit-identical to them (a stable partition's permutation is unique).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.partition import Partitioned
from repro.kernels.partition import partition as kernels


@functools.partial(
    jax.jit, static_argnames=("n_cols", "block_tags", "block_rows", "interpret")
)
def partition_tags(
    col_tag: jax.Array,
    n_cols: int,
    *,
    block_tags: int = kernels.DEFAULT_BLOCK_TAGS,
    block_rows: int = kernels.DEFAULT_BLOCK_ROWS,
    interpret: bool,
) -> Partitioned:
    """Kernel-backed equivalent of ``core.partition.partition_scatter2``."""
    n = col_tag.shape[0]
    if n == 0:  # degenerate but public: match the jnp impls' empty output
        zeros = jnp.zeros((n_cols + 1,), jnp.int32)
        return Partitioned(jnp.zeros((0,), jnp.int32), zeros, zeros)
    bn = min(block_tags, n) or 1
    nb = -(-n // bn)
    br = min(block_rows, nb)              # don't pad small streams up to a
                                          # full grid step of sentinel blocks
    nbp = -(-nb // br) * br               # pad blocks up to the grid step
    pad = nbp * bn - n
    tags = col_tag.astype(jnp.int32)
    if pad:
        tags = jnp.concatenate([tags, jnp.full((pad,), n_cols, jnp.int32)])

    rel, count = kernels.partition_blocks(
        tags.reshape(nbp, bn), n_cols, block_rows=br, interpret=interpret
    )

    # Tiny glue: global column starts from the totals, then lift the
    # column-relative destinations.  Sentinel padding is trailing, so it
    # only inflates the last column's count (corrected below) and no real
    # symbol's start or rank.
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(count)[:-1]])
    count = count.at[-1].add(-pad)
    dest = (start[tags] + rel.reshape(-1))[:n]

    # The radix pass's scatter: invert dest into gather form (XLA owns the
    # irregular write — see kernels/partition/partition.py docstring).
    # This is the staged path's one remaining HBM round-trip; the fused
    # whole-pipeline megakernel (kernels/fused_pipeline/) never builds the
    # permutation at all — it consumes dest directly in apply form
    # (css[dest[i]] = sym[i]), which is equivalent because dest is the
    # inverse of perm by construction.
    perm = jnp.zeros((n,), jnp.int32).at[dest].set(jnp.arange(n, dtype=jnp.int32))
    return Partitioned(perm, start.astype(jnp.int32), count.astype(jnp.int32))
