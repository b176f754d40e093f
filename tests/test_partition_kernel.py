"""Partition kernel validation (kernels/partition): bit-identical to every
jnp partition impl, stable, and drop-in across the end-to-end pipeline.

A stable partition's permutation is unique, so the kernel must agree with
``partition_argsort`` / ``partition_scatter`` / ``partition_scatter2``
*exactly* — perm, col_start and col_count — for any tag stream, including
ones that do not divide the kernel's block sizes.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Parser, ParserConfig, Schema, make_csv_dfa
from repro.core import partition as partition_mod
from repro.core.backends import interpret_kernels
from repro.kernels.partition import ops as part_ops
from repro.kernels.partition import partition as part_kernels
from repro.kernels.partition import ref as part_ref
from tests.test_backend_parity import _assert_results_equal

# n exercises: < one block, exact block multiples, straggler blocks, and
# grid-step (block_rows) padding; c covers tiny and paper-sized widths.
SIZES = [(1, 1), (513, 5), (4096, 17), (4100, 2), (9999, 8)]


@pytest.mark.parametrize("n,c", SIZES)
def test_kernel_matches_all_jnp_impls(n, c):
    # local generator: the session `rng` fixture's stream is order-sensitive
    rng = np.random.default_rng(n * 31 + c)
    tags = jnp.asarray(rng.integers(0, c + 1, size=n), jnp.int32)  # incl. sentinel
    got = part_ops.partition_tags(tags, c, interpret=interpret_kernels())
    oracle = part_ref.partition_tags(tags, c)
    for name, impl in {**partition_mod.PARTITION_IMPLS, "ref": lambda t, k: oracle}.items():
        want = impl(tags, c)
        np.testing.assert_array_equal(
            np.asarray(got.perm), np.asarray(want.perm), err_msg=f"perm vs {name}")
        np.testing.assert_array_equal(
            np.asarray(got.col_start), np.asarray(want.col_start),
            err_msg=f"col_start vs {name}")
        np.testing.assert_array_equal(
            np.asarray(got.col_count), np.asarray(want.col_count),
            err_msg=f"col_count vs {name}")


def test_kernel_nondefault_blocks():
    """Straggler tags + straggler blocks under tiny block sizes."""
    n, c = 1000, 4
    rng = np.random.default_rng(7)
    tags = jnp.asarray(rng.integers(0, c + 1, size=n), jnp.int32)
    want = partition_mod.partition_argsort(tags, c)
    for bn, br in [(64, 2), (1000, 1), (2048, 4)]:
        got = part_ops.partition_tags(tags, c, block_tags=bn, block_rows=br,
                                      interpret=interpret_kernels())
        np.testing.assert_array_equal(
            np.asarray(got.perm), np.asarray(want.perm), err_msg=f"bn={bn},br={br}")
        np.testing.assert_array_equal(
            np.asarray(got.col_count), np.asarray(want.col_count),
            err_msg=f"bn={bn},br={br}")


def test_kernel_degenerate_streams():
    c = 3
    for tags_np in (np.zeros(300, np.int32),            # all one column
                    np.full(300, c, np.int32),          # all sentinel (dropped)
                    np.arange(300, dtype=np.int32) % (c + 1)):
        tags = jnp.asarray(tags_np)
        got = part_ops.partition_tags(tags, c, interpret=interpret_kernels())
        want = partition_mod.partition_scatter(tags, c)
        np.testing.assert_array_equal(np.asarray(got.perm), np.asarray(want.perm))
        np.testing.assert_array_equal(
            np.asarray(got.col_count), np.asarray(want.col_count))


def test_partition_blocks_counts_and_rel():
    """The kernel's carry totals match the tag histogram and its relative
    destinations are exactly each tag's # of earlier same-column tags."""
    n, c, bn = 2048, 6, 256
    rng = np.random.default_rng(11)
    tags_np = rng.integers(0, c + 1, size=n).astype(np.int32)
    tags = jnp.asarray(tags_np)
    rel, count = part_kernels.partition_blocks(tags.reshape(n // bn, bn), c,
                                               block_rows=4, interpret=interpret_kernels())
    np.testing.assert_array_equal(
        np.asarray(count), np.asarray(partition_mod.column_histogram(tags, c)))
    want_rel = np.empty(n, np.int32)
    seen = np.zeros(c + 1, np.int32)
    for i, t in enumerate(tags_np):
        want_rel[i] = seen[t]
        seen[t] += 1
    np.testing.assert_array_equal(np.asarray(rel).reshape(-1), want_rel)


@pytest.mark.parametrize("tagging", ["tagged", "inline", "vector"])
def test_end_to_end_kernel_partition_parity(tagging):
    """The kernel partition must produce ParseResults identical to a jnp
    impl in both tagged and terminated materialization modes.  (That this
    extends to *every* impl follows from the unit-level perm/start/count
    parity above — identical Partitioned outputs imply identical parses —
    so e2e only needs the kernel wiring itself, keeping tier-1 cheap.)"""
    data = (b'1,"a,b",3.5,2024-02-29\n'
            b'-42,"he""llo",0.25,2023-02-29\n'
            b',world,1e3,2024-04-31\n'
            b'7,x,,2024-12-31 23:59:59\n')
    schema = Schema.of(("i", "int32"), ("s", "str"), ("f", "float32"),
                       ("d", "date"))

    def parse(partition_impl):
        cfg = ParserConfig(dfa=make_csv_dfa(), schema=schema, max_records=16,
                           chunk_size=16, tagging=tagging, backend="pallas",
                           partition_impl=partition_impl)
        return Parser(cfg).parse(data)

    _assert_results_equal(parse("argsort"), parse("kernel"),
                          label=f"{tagging}/kernel: ")


def test_reference_backend_rejects_kernel_impl():
    schema = Schema.of(("a", "int32"))
    with pytest.raises(ValueError, match="partition_impl"):
        ParserConfig(dfa=make_csv_dfa(), schema=schema, max_records=4,
                     partition_impl="kernel")


# ---------------------------------------------------------------------------
# property: stability (equal col_tags keep input order)
# ---------------------------------------------------------------------------

def test_kernel_partition_stable_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    # n from a fixed set (distinct shapes recompile the jit'd kernel);
    # boundary-straddling sizes for bn=128, br=4.
    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 2**32 - 1),
           st.sampled_from([1, 5, 127, 128, 129, 500, 512, 513]),
           st.sampled_from([1, 3, 8]))
    def check(seed, n, c):
        rng = np.random.default_rng(seed)
        tags = jnp.asarray(rng.integers(0, c + 1, size=n), jnp.int32)
        got = part_ops.partition_tags(tags, c, block_tags=128, block_rows=4,
                                      interpret=interpret_kernels())
        perm = np.asarray(got.perm)
        tags_np = np.asarray(tags)
        # permutation correctness
        assert sorted(perm.tolist()) == list(range(n))
        # partition: tags appear in nondecreasing column order
        assert (np.diff(tags_np[perm]) >= 0).all()
        # stability: within every column, source indices stay increasing
        for col in range(c + 1):
            src = perm[tags_np[perm] == col]
            if src.size > 1:
                assert (np.diff(src) > 0).all()
        # histogram bookkeeping matches the permutation
        start, count = np.asarray(got.col_start), np.asarray(got.col_count)
        np.testing.assert_array_equal(count, np.bincount(tags_np, minlength=c + 1))
        np.testing.assert_array_equal(start, np.concatenate([[0], np.cumsum(count)[:-1]]))

    check()
