"""Partition kernel validation (kernels/partition): bit-identical to every
jnp partition impl, stable, and drop-in across the end-to-end pipeline.

A stable partition's permutation is unique, so the kernel must agree with
``partition_argsort`` / ``partition_scatter`` / ``partition_scatter2``
*exactly* — perm, col_start and col_count — for any tag stream, including
ones that do not divide the kernel's block sizes.
"""
import jax
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


# ---------------------------------------------------------------------------
# partition_impl="sort": one stable sort carrying the payloads, no permutation
# ---------------------------------------------------------------------------

def _payloads(rng, n):
    """symbol (u8), rec_tag (i32) and delim_flag (bool), as tagging makes
    them; ``tagged`` mode carries the first two, ``inline``/``vector`` all
    three."""
    return (jnp.asarray(rng.integers(0, 256, size=n), jnp.uint8),
            jnp.asarray(np.sort(rng.integers(0, max(n // 4, 1), size=n)),
                        jnp.int32),
            jnp.asarray(rng.integers(0, 2, size=n).astype(bool)))


def _assert_sort_matches(tags, c, payloads, want_part, label):
    start, count = partition_mod.column_layout(tags, c)
    got = partition_mod.sort_partition(tags, c, *payloads)
    want = partition_mod.apply_partition(want_part.perm, tags, *payloads)
    want = want if isinstance(want, tuple) else (want,)
    assert len(got) == len(want) == len(payloads) + 1, label
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == w.dtype, f"{label}: output {i} dtype"
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{label}: output {i}")
    np.testing.assert_array_equal(np.asarray(start),
                                  np.asarray(want_part.col_start),
                                  err_msg=f"{label}: col_start")
    np.testing.assert_array_equal(np.asarray(count),
                                  np.asarray(want_part.col_count),
                                  err_msg=f"{label}: col_count")


@pytest.mark.parametrize("n,c", SIZES + [(0, 3)])
@pytest.mark.parametrize("mode", ["tagged", "inline", "vector"])
def test_sort_matches_every_gather_impl(n, c, mode):
    """col_sorted, css, rec_sorted, flag_sorted, col_start and col_count of
    the sort are exactly ``apply_partition`` through the permutation of
    every gather-form impl and of the radix kernel."""
    rng = np.random.default_rng(n * 31 + c)
    tags = jnp.asarray(rng.integers(0, c + 1, size=n), jnp.int32)
    payloads = _payloads(rng, n)[:2 if mode == "tagged" else 3]
    impls = {**partition_mod.PARTITION_IMPLS,
             "kernel": lambda t, k: part_ops.partition_tags(
                 t, k, interpret=interpret_kernels())}
    for name, impl in impls.items():
        _assert_sort_matches(tags, c, payloads, impl(tags, c),
                             f"n={n} c={c} {mode} vs {name}")


@pytest.mark.parametrize("kind", ["one_column", "sentinel", "round_robin"])
def test_sort_degenerate_streams(kind):
    c, n = 3, 300
    tags_np = {"one_column": np.zeros(n, np.int32),
               "sentinel": np.full(n, c, np.int32),
               "round_robin": np.arange(n, dtype=np.int32) % (c + 1)}[kind]
    tags = jnp.asarray(tags_np)
    payloads = _payloads(np.random.default_rng(3), n)
    _assert_sort_matches(tags, c, payloads,
                         partition_mod.partition_scatter(tags, c), kind)


@pytest.mark.parametrize("n,c", SIZES)
def test_sort_stable_fallback(n, c):
    """Where ``col_tag * n + position`` could pass int32 the sort keeps the
    stable sort on the tag itself: the same outputs.  A column count that
    large is reached here by the static ``n_cols`` alone; the tags stay
    within it."""
    rng = np.random.default_rng(n * 7 + c)
    tags = jnp.asarray(rng.integers(0, c + 1, size=n), jnp.int32)
    payloads = _payloads(rng, n)
    huge = -(-2**31 // n)                   # (huge + 1) * n > 2**31
    got = partition_mod.sort_partition(tags, huge, *payloads)
    want = partition_mod.sort_partition(tags, c, *payloads)
    ref = partition_mod.apply_partition(
        partition_mod.partition_argsort(tags, c).perm, tags, *payloads)
    for g, w, r in zip(got, want, ref):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_sort_every_column_count():
    """n_cols from 1 to 31, each against ``partition_argsort`` and numpy's
    stable argsort and histogram."""
    n = 257
    rng = np.random.default_rng(5)
    payloads = _payloads(rng, n)
    for c in range(1, 32):
        tags_np = rng.integers(0, c + 1, size=n).astype(np.int32)
        tags = jnp.asarray(tags_np)
        _assert_sort_matches(tags, c, payloads,
                             partition_mod.partition_argsort(tags, c),
                             f"c={c}")
        order = np.argsort(tags_np, kind="stable")
        got = partition_mod.sort_partition(tags, c, *payloads)
        for g, a in zip(got, (tags_np,) + payloads):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(a)[order],
                                          err_msg=f"c={c} vs numpy")
        np.testing.assert_array_equal(
            np.asarray(partition_mod.column_layout(tags, c)[1]),
            np.bincount(tags_np, minlength=c + 1), err_msg=f"c={c} counts")


def test_sort_under_vmap():
    """Batched streams (``StreamSession``'s ``vmap``) sort each lane on its
    own: lane by lane equal to the unbatched sort and layout."""
    lanes, n, c = 3, 700, 6
    rng = np.random.default_rng(9)
    tags = jnp.asarray(rng.integers(0, c + 1, size=(lanes, n)), jnp.int32)
    sym = jnp.asarray(rng.integers(0, 256, size=(lanes, n)), jnp.uint8)
    rec = jnp.asarray(rng.integers(0, 50, size=(lanes, n)), jnp.int32)
    got = jax.vmap(
        lambda t, s, r: partition_mod.sort_partition(t, c, s, r))(tags, sym, rec)
    start, count = jax.vmap(
        lambda t: partition_mod.column_layout(t, c))(tags)
    for lane in range(lanes):
        want = partition_mod.partition_scatter(tags[lane], c)
        w = partition_mod.apply_partition(want.perm, tags[lane], sym[lane],
                                          rec[lane])
        for g, x in zip(got, w):
            np.testing.assert_array_equal(np.asarray(g[lane]), np.asarray(x))
        np.testing.assert_array_equal(np.asarray(start[lane]),
                                      np.asarray(want.col_start))
        np.testing.assert_array_equal(np.asarray(count[lane]),
                                      np.asarray(want.col_count))
