"""Associativity + equivalence properties of ParPaRaw's two semigroups
(paper §3.1 composite, §3.2 abs/rel column offsets)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import offsets as offs
from repro.core import transition as tr

S = 6


@settings(max_examples=50, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_compose_associative(seed):
    rng = np.random.default_rng(seed)
    a, b, c = (jnp.asarray(rng.integers(0, S, size=S), jnp.int32) for _ in range(3))
    lhs = tr.compose(tr.compose(a, b), c)
    rhs = tr.compose(a, tr.compose(b, c))
    np.testing.assert_array_equal(np.asarray(lhs), np.asarray(rhs))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(1, 64))
def test_matmul_scan_equals_gather_scan(seed, n):
    rng = np.random.default_rng(seed)
    vecs = jnp.asarray(rng.integers(0, S, size=(n, S)), jnp.int32)
    g = tr.exclusive_scan_vectors(vecs, use_matmul=False)
    m = tr.exclusive_scan_vectors(vecs, use_matmul=True)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(m))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(1, 33))
def test_fold_equals_sequential_fold(seed, n):
    rng = np.random.default_rng(seed)
    vecs_np = rng.integers(0, S, size=(n, S)).astype(np.int32)
    ref = np.arange(S)
    for v in vecs_np:
        ref = v[ref]
    out = tr.fold_vectors(jnp.asarray(vecs_np))
    np.testing.assert_array_equal(np.asarray(out), ref)


@settings(max_examples=50, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_column_offset_op_associative(seed):
    rng = np.random.default_rng(seed)

    def rand():
        return (
            jnp.asarray(rng.integers(0, 2), jnp.int32),
            jnp.asarray(rng.integers(0, 100), jnp.int32),
        )

    a, b, c = rand(), rand(), rand()
    l = offs.combine_col(offs.combine_col(a, b), c)
    r = offs.combine_col(a, offs.combine_col(b, c))
    assert int(l[0]) == int(r[0]) and int(l[1]) == int(r[1])


def _naive_ids(classes: np.ndarray):
    rid = np.zeros(classes.size, np.int32)
    cid = np.zeros(classes.size, np.int32)
    r = c = 0
    for i, cl in enumerate(classes):
        rid[i], cid[i] = r, c
        if cl == 2:  # RECORD_DELIM
            r += 1
            c = 0
        elif cl == 1:  # FIELD_DELIM
            c += 1
    return rid, cid


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(1, 200))
def test_symbol_ids_match_naive(seed, n):
    rng = np.random.default_rng(seed)
    classes = rng.choice([0, 1, 2, 3], size=n, p=[0.6, 0.2, 0.1, 0.1]).astype(np.uint8)
    rid_ref, cid_ref = _naive_ids(classes)
    ids = offs.symbol_ids(jnp.asarray(classes))
    np.testing.assert_array_equal(np.asarray(ids.record_id), rid_ref)
    np.testing.assert_array_equal(np.asarray(ids.column_id), cid_ref)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(1, 16), st.integers(1, 32))
def test_chunked_ids_match_flat(seed, c, k):
    """Two-level (chunk summaries + scan) ids == flat symbol ids.

    This is the exact decomposition the distributed parser uses across
    devices, so equality here is the correctness core of core/distributed."""
    rng = np.random.default_rng(seed)
    classes = rng.choice([0, 1, 2, 3], size=(c, k), p=[0.6, 0.2, 0.1, 0.1]).astype(np.uint8)
    flat = offs.symbol_ids(jnp.asarray(classes.reshape(-1)))
    summ = offs.chunk_summaries(jnp.asarray(classes))
    chunk_offs = offs.scan_chunk_offsets(summ)
    two = offs.symbol_ids_from_chunks(jnp.asarray(classes), chunk_offs)
    np.testing.assert_array_equal(np.asarray(two.record_id), np.asarray(flat.record_id))
    np.testing.assert_array_equal(np.asarray(two.column_id), np.asarray(flat.column_id))
    assert int(two.n_records) == int(flat.n_records)


@pytest.mark.parametrize("shape,axis", [
    ((1,), 0), ((128,), 0), ((129,), 0), ((2000,), 0),    # around BLOCK
    ((37, 300), 1), ((300, 7), 0), ((3, 17000), 1),   # two recursion levels
])
def test_blocked_scans_match_jnp(shape, axis):
    """``core.scans`` (blocked associative scans) equal ``jnp.cumsum`` /
    ``lax.cummax`` bit for bit, and the blocked composite scan equals the
    flat ``lax.associative_scan`` of ``transition.compose``."""
    from repro.core import scans

    rng = np.random.default_rng(sum(shape))
    x = jnp.asarray(rng.integers(-50, 50, size=shape), jnp.int32)
    vecs = jnp.asarray(rng.integers(0, S, size=(shape[0], S)), jnp.int32)
    got = jax.jit(lambda x, v: (
        scans.cumsum(x, axis), scans.cummax(x, axis),
        scans.scan(tr.compose, v, tr.identity_vector(S))))(x, vecs)
    want = jax.jit(lambda x, v: (
        jnp.cumsum(x, axis=axis), jax.lax.cummax(x, axis=axis),
        jax.lax.associative_scan(tr.compose, v, axis=0)))(x, vecs)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
