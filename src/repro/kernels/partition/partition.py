"""Pallas TPU kernel for the §3.3 stable partition (paper's radix pass).

The paper partitions the tagged symbol stream with one stable radix-sort
pass over column tags (CUB): per-block column histograms, an exclusive
prefix over (block, column), then a scatter to each symbol's destination.
On TPU the whole counting half collapses into ONE kernel, because Pallas
grids execute *sequentially*: a VMEM carry of per-column counts persists
across grid steps, so each step can histogram its blocks, take the
exclusive running prefix (the decoupled-lookback analogue — no second
global pass), rank every tag inside its block, and emit each symbol's
*column-relative* destination in a single sweep:

    rel[i] = (# earlier symbols with the same column tag)

Mosaic has no ``cumsum``, so both exclusive prefixes are triangular
matmuls on the MXU, one column at a time: for the column's 0/1 mask ``m``
of a ``(block_rows, block_tags)`` step, ``m @ U`` (``U`` strictly upper
triangular) counts the same-column tags earlier in each block and the
lane sum of ``L @ m`` (``L`` strictly lower triangular) those in earlier
blocks of the step.  The masks are exact in bfloat16 and the products
accumulate in float32, whose integers are exact far beyond one step's
``block_rows * block_tags`` tags; the running carry across steps stays
int32.  The column axis is tiny (≤ a few dozen) and is unrolled.

What stays in XLA glue (``ops.partition_tags``): turning the carry's final
value into global column starts (an ``n_cols+1``-sized exclusive cumsum),
``dest = start[tag] + rel``, and the one global scatter that materialises
the permutation — TPU vector lanes cannot scatter to HBM per-lane, so the
irregular write is the one step the kernel cannot own (same division of
labour as the CSS gather in ``kernels.numparse``).

Shape contract: ``tags (NB, BN) int32`` with NB a multiple of
``block_rows``; callers pad with the sentinel column ``n_cols`` (inert:
trailing sentinel padding ranks past every real sentinel symbol).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: Tags per partition block (the paper's thread-block tile).
DEFAULT_BLOCK_TAGS = 256
#: Blocks per grid step (bench-tuned with DEFAULT_BLOCK_TAGS against the
#: jnp impls at yelp/taxi sizes — smaller blocks keep the one-hot cumsum
#: cheap, more rows per step amortise dispatch; BENCH_parser.json).
DEFAULT_BLOCK_ROWS = 64


def _make_partition_kernel(n_parts: int, block_rows: int, block_tags: int):
    def kernel(tags_ref, rel_ref, count_ref, carry_ref):
        # carry_ref (1, n_parts) VMEM scratch: per-column count of all tags
        # in earlier grid steps.  Grids run sequentially on TPU (and in the
        # interpreter), which is what makes the single-pass scan sound.
        @pl.when(pl.program_id(0) == 0)
        def _():
            carry_ref[...] = jnp.zeros((1, n_parts), jnp.int32)

        def tri(n, strict_upper):
            i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
            return ((i < j) if strict_upper else (j < i)).astype(jnp.bfloat16)

        upper = tri(block_tags, True)     # U[j, n] = j < n
        lower = tri(block_rows, False)    # L[r, q] = q < r
        dot = lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, n_parts), 1)

        tags = tags_ref[...]                          # (BR, BN)
        carry = carry_ref[...]                        # (1, C+1)
        rel = jnp.zeros(tags.shape, jnp.int32)
        step_count = jnp.zeros((1, n_parts), jnp.int32)
        for p in range(n_parts):
            hit = tags == p
            m = hit.astype(jnp.bfloat16)
            # Stable rank: same-column tags earlier in this block, plus
            # those in earlier blocks of this step, plus earlier steps.
            in_block = dot(m, upper)                              # (BR, BN)
            earlier_blocks = jnp.sum(dot(lower, m), axis=1, keepdims=True)
            rank = (in_block + earlier_blocks).astype(jnp.int32)
            rel = jnp.where(hit, rank + carry[:, p:p + 1], rel)
            total = jnp.sum(jnp.sum(m.astype(jnp.float32), axis=1,
                                    keepdims=True), axis=0, keepdims=True)
            step_count = jnp.where(lane == p, total.astype(jnp.int32),
                                   step_count)
        rel_ref[...] = rel

        carry_ref[...] = carry + step_count
        count_ref[...] = carry_ref[...]               # last step's write wins

    return kernel


def partition_blocks(
    tags: jax.Array,
    n_cols: int,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool,
):
    """``(NB, BN) int32`` blocked tags → column-relative destinations
    ``(NB, BN) int32`` plus total per-column counts ``(n_cols+1,) int32``
    (sentinel drop column included)."""
    nb, bn = tags.shape
    br = min(block_rows, nb)
    if nb % br:
        raise ValueError(f"blocks {nb} not a multiple of block_rows {br}")
    n_parts = n_cols + 1
    kernel = _make_partition_kernel(n_parts, br, bn)
    rel, count = pl.pallas_call(
        kernel,
        grid=(nb // br,),
        in_specs=[pl.BlockSpec((br, bn), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((br, bn), lambda i: (i, 0)),
            pl.BlockSpec((1, n_parts), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, bn), jnp.int32),
            jax.ShapeDtypeStruct((1, n_parts), jnp.int32),
        ],
        scratch_shapes=[pltpu_vmem((1, n_parts), jnp.int32)],
        interpret=interpret,
        name="radix_partition",
    )(tags)
    return rel, count[0]


def pltpu_vmem(shape, dtype):
    """VMEM scratch spec; the deferred import keeps ``pallas.tpu`` off the
    module-import path (it is only touched when a kernel is actually built).
    """
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)
