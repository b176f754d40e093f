"""Per-kernel interpret-mode validation: shape/dtype sweeps vs ref.py oracles."""
import numpy as np
import jax.numpy as jnp
import pytest

from jaxpr_utils import gathers_outside_pallas as _gathers_outside_pallas
from repro.core import make_csv_dfa, make_log_dfa, make_simple_dfa
from repro.core.backends import interpret_kernels

# ---------------------------------------------------------------------------
# dfa_scan
# ---------------------------------------------------------------------------

DFAS = {"csv": make_csv_dfa(), "clf": make_log_dfa(), "simple": make_simple_dfa()}


@pytest.mark.parametrize("dfa_name", list(DFAS))
@pytest.mark.parametrize("n_chunks,chunk_bytes,block", [
    (64, 32, 64), (256, 64, 128), (128, 31, 32), (512, 16, 256),
])
def test_dfa_scan_chunk_vectors(rng, dfa_name, n_chunks, chunk_bytes, block):
    from repro.kernels.dfa_scan import ops, ref
    dfa = DFAS[dfa_name]
    alphabet = np.frombuffer(b',"\n# ab[]\t', np.uint8)
    chunks = jnp.asarray(
        alphabet[rng.integers(0, len(alphabet), size=n_chunks * chunk_bytes)]
        .reshape(n_chunks, chunk_bytes)
    )
    got = ops.chunk_vectors(chunks, dfa, block_chunks=block,
                            interpret=interpret_kernels())
    want = ref.chunk_vectors(chunks, dfa)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dfa_name", list(DFAS))
def test_dfa_scan_replay(rng, dfa_name):
    from repro.kernels.dfa_scan import ops, ref
    dfa = DFAS[dfa_name]
    alphabet = np.frombuffer(b',"\n#xy z', np.uint8)
    chunks = jnp.asarray(
        alphabet[rng.integers(0, len(alphabet), size=256 * 48)].reshape(256, 48)
    )
    starts = jnp.asarray(rng.integers(0, dfa.n_states, size=256), jnp.int32)
    c_k, e_k = ops.replay(chunks, starts, dfa, block_chunks=64,
                          interpret=interpret_kernels())
    c_r, e_r = ref.replay(chunks, starts, dfa)
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))
    np.testing.assert_array_equal(np.asarray(e_k), np.asarray(e_r))


def test_dfa_scan_end_to_end_matches_pipeline(rng):
    from repro.kernels.dfa_scan import ops
    from repro.core.transition import transition_pipeline
    dfa = DFAS["csv"]
    alphabet = np.frombuffer(b',"\nabc', np.uint8)
    chunks = jnp.asarray(
        alphabet[rng.integers(0, len(alphabet), size=512 * 64)].reshape(512, 64)
    )
    cls_k, _ = ops.parse_classes(chunks, dfa, interpret=interpret_kernels())
    cls_j, _, _ = transition_pipeline(chunks, dfa)
    np.testing.assert_array_equal(np.asarray(cls_k), np.asarray(cls_j))


# ---------------------------------------------------------------------------
# numparse
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("width", [8, 11, 16])
@pytest.mark.parametrize("rows,block", [(512, 128), (1024, 512)])
def test_numparse_int(rng, width, rows, block):
    from repro.kernels.numparse import ops as k_ops
    from repro.kernels.numparse import ref as k_ref
    # mixture of valid ints, junk, empties
    strs = []
    for _ in range(rows):
        u = rng.random()
        if u < 0.6:
            strs.append(str(int(rng.integers(-10**8, 10**8))))
        elif u < 0.75:
            strs.append("x1y")
        elif u < 0.85:
            strs.append("")
        else:
            strs.append("+%d" % int(rng.integers(0, 10**6)))
    byts = np.zeros((rows, width), np.uint8)
    lens = np.zeros((rows,), np.int32)
    for i, s in enumerate(strs):
        bs = s.encode()[:width]
        byts[i, : len(bs)] = np.frombuffer(bs, np.uint8)
        lens[i] = len(bs)
    got_v, got_ok = k_ops.parse_int_fields(jnp.asarray(byts), jnp.asarray(lens), block_rows=block,
                                           interpret=interpret_kernels())
    want_v, want_ok = k_ref.parse_int_fields(jnp.asarray(byts), jnp.asarray(lens))
    np.testing.assert_array_equal(np.asarray(got_ok), np.asarray(want_ok))
    ok = np.asarray(got_ok)
    np.testing.assert_array_equal(np.asarray(got_v)[ok], np.asarray(want_v)[ok])
    # cross-check against python int() (on the width-truncated field the
    # kernel actually saw)
    for i, s in enumerate(strs):
        if ok[i]:
            assert int(np.asarray(got_v)[i]) == int(s[:width]), (i, s)


def _pack_rows(strs, width):
    byts = np.zeros((len(strs), width), np.uint8)
    lens = np.zeros((len(strs),), np.int32)
    for i, s in enumerate(strs):
        bs = s.encode()[:width]
        byts[i, : len(bs)] = np.frombuffer(bs, np.uint8)
        lens[i] = len(bs)
    return jnp.asarray(byts), jnp.asarray(lens)


def test_numparse_int_overflow(rng):
    """Magnitude overflow clears ok on the kernel exactly like the jnp ref."""
    from repro.kernels.numparse import ops as k_ops
    from repro.kernels.numparse import ref as k_ref
    strs = ["2147483647", "-2147483647", "2147483648", "-2147483648",
            "9999999999", "0000000001", "00000000000042", "12345678901"]
    strs += [str(int(v)) for v in rng.integers(2**31 - 100, 2**31 + 100, size=24)]
    byts, lens = _pack_rows(strs, 16)
    got_v, got_ok = k_ops.parse_int_fields(byts, lens, block_rows=len(strs),
                                           interpret=interpret_kernels())
    want_v, want_ok = k_ref.parse_int_fields(byts, lens)
    np.testing.assert_array_equal(np.asarray(got_ok), np.asarray(want_ok))
    ok = np.asarray(got_ok)
    np.testing.assert_array_equal(np.asarray(got_v)[ok], np.asarray(want_v)[ok])
    for s, o in zip(strs, ok):
        assert bool(o) == (abs(int(s)) <= 2**31 - 1), s


@pytest.mark.parametrize("width", [16, 24])
@pytest.mark.parametrize("rows,block", [(512, 128), (256, 256)])
def test_numparse_float(rng, width, rows, block):
    """Float kernel is bit-identical to the jnp reference — values AND ok."""
    from repro.kernels.numparse import ops as k_ops
    from repro.kernels.numparse import ref as k_ref
    strs = []
    for _ in range(rows):
        u = rng.random()
        if u < 0.45:
            strs.append(f"{rng.normal() * 10.0 ** int(rng.integers(-6, 7)):.6g}")
        elif u < 0.6:
            strs.append(f"{rng.integers(-1000, 1000)}e{rng.integers(-40, 41)}")
        elif u < 0.7:
            strs.append(rng.choice([".", "+.5", "-.", "3.", "1e", "1e+", "1.2.3",
                                    "1e39", "-1e-39", "+", ""]))
        elif u < 0.8:
            strs.append("x%.2f" % rng.random())
        else:
            strs.append(str(int(rng.integers(-10**9, 10**9))))
    byts, lens = _pack_rows(strs, width)
    got_v, got_ok = k_ops.parse_float_fields(byts, lens, block_rows=block,
                                             interpret=interpret_kernels())
    want_v, want_ok = k_ref.parse_float_fields(byts, lens)
    np.testing.assert_array_equal(np.asarray(got_ok), np.asarray(want_ok))
    ok = np.asarray(got_ok)
    # bit-for-bit on parsed values (inf from overflow included)
    np.testing.assert_array_equal(np.asarray(got_v)[ok], np.asarray(want_v)[ok])


@pytest.mark.parametrize("rows,block", [(512, 128), (100, 50)])
def test_numparse_date(rng, rows, block):
    """Date kernel is bit-identical to the jnp reference — values AND ok."""
    from repro.kernels.numparse import ops as k_ops
    from repro.kernels.numparse import ref as k_ref
    strs = []
    for _ in range(rows):
        u = rng.random()
        y, m, d = rng.integers(1902, 2038), rng.integers(1, 13), rng.integers(1, 32)
        if u < 0.5:
            strs.append(f"{y:04d}-{m:02d}-{d:02d}")
        elif u < 0.8:
            hh, mm, ss = rng.integers(0, 25), rng.integers(0, 61), rng.integers(0, 61)
            sep = " " if rng.random() < 0.8 else "T"
            strs.append(f"{y:04d}-{m:02d}-{d:02d}{sep}{hh:02d}:{mm:02d}:{ss:02d}")
        else:
            strs.append(rng.choice(["", "junk", "2024-1-01", "2024/01/01",
                                    "2024-01-01x00:00:00", "2024-00-10"]))
    byts, lens = _pack_rows(strs, 19)
    got_v, got_ok = k_ops.parse_date_fields(byts, lens, block_rows=block,
                                            interpret=interpret_kernels())
    want_v, want_ok = k_ref.parse_date_fields(byts, lens)
    np.testing.assert_array_equal(np.asarray(got_ok), np.asarray(want_ok))
    np.testing.assert_array_equal(np.asarray(got_v)[np.asarray(got_ok)],
                                  np.asarray(want_v)[np.asarray(got_ok)])


# ---------------------------------------------------------------------------
# numparse — fused gather+convert variants
# ---------------------------------------------------------------------------

def _pack_css(strs):
    """Concatenate field strings into a CSS + (offset, length) index."""
    lens = np.asarray([len(s) for s in strs], np.int32)
    offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    css = np.frombuffer("".join(strs).encode(), np.uint8)
    if css.size == 0:
        css = np.zeros(1, np.uint8)
    return jnp.asarray(css), jnp.asarray(offs), jnp.asarray(lens)


def _fused_cases(rng, rows):
    ints, floats, dates = [], [], []
    for _ in range(rows):
        u = rng.random()
        if u < 0.15:
            junk = rng.choice(["", "x1y", "+", ".", "1e", "9" * 12, "2024-13-01"])
            ints.append(junk); floats.append(junk); dates.append(junk)
            continue
        ints.append(str(int(rng.integers(-(2**33), 2**33))))
        floats.append(f"{rng.normal() * 10.0 ** int(rng.integers(-6, 7)):.6g}")
        y, m, d = rng.integers(1970, 2038), rng.integers(1, 13), rng.integers(1, 32)
        dates.append(f"{y:04d}-{m:02d}-{d:02d}" if rng.random() < 0.5 else
                     f"{y:04d}-{m:02d}-{d:02d} {rng.integers(0,24):02d}:"
                     f"{rng.integers(0,60):02d}:{rng.integers(0,60):02d}")
    return ints, floats, dates


@pytest.mark.parametrize("rows,block", [(500, 128), (512, 512), (33, 16)])
def test_numparse_fused_matches_unfused_and_typeconv(rows, block):
    """The fused (css, offset, length) kernels are bit-identical to the
    unfused gather+kernel path AND to the jnp typeconv oracle — value,
    valid and empty alike."""
    from repro.core import typeconv
    from repro.kernels.numparse import ops as k_ops
    # local generator: the session `rng` fixture's stream is order-sensitive
    ints, floats, dates = _fused_cases(np.random.default_rng(rows + block), rows)
    cases = [
        (ints, k_ops.parse_int_column_fused, k_ops.parse_int_column,
         lambda c, o, l: typeconv.parse_int(c, o, l, width=11)),
        (floats, k_ops.parse_float_column_fused, k_ops.parse_float_column,
         lambda c, o, l: typeconv.parse_float(c, o, l, width=24)),
        (dates, k_ops.parse_date_column_fused, k_ops.parse_date_column,
         typeconv.parse_date),
    ]
    for strs, fused, unfused, oracle in cases:
        css, offs, lens = _pack_css(strs)
        got = fused(css, offs, lens, window_rows=block, interpret=interpret_kernels())
        # vs the unfused kernel: bit-identical on everything (shared arith).
        want = unfused(css, offs, lens, block_rows=block, interpret=interpret_kernels())
        for f in ("value", "valid", "empty"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
                err_msg=f"{fused.__name__} vs unfused: {f}")
        # vs typeconv: valid/empty exact; values where valid (the garbage
        # value of an *invalid* field is unspecified across Horner variants
        # — stages.materialize normalises it to 0 before anyone sees it).
        ref = oracle(css, offs, lens)
        np.testing.assert_array_equal(np.asarray(got.valid), np.asarray(ref.valid),
                                      err_msg=f"{fused.__name__} vs typeconv: valid")
        np.testing.assert_array_equal(np.asarray(got.empty), np.asarray(ref.empty),
                                      err_msg=f"{fused.__name__} vs typeconv: empty")
        ok = np.asarray(got.valid)
        np.testing.assert_array_equal(np.asarray(got.value)[ok],
                                      np.asarray(ref.value)[ok],
                                      err_msg=f"{fused.__name__} vs typeconv: value")


def test_numparse_fused_field_at_css_end():
    """Fields touching the last CSS byte must not read out of bounds (the
    fused kernels width-pad the buffer; the unfused gather clamps)."""
    from repro.kernels.numparse import ops as k_ops
    strs = ["123", "-45", "678"]
    css, offs, lens = _pack_css(strs)
    got = k_ops.parse_int_column_fused(css, offs, lens, interpret=interpret_kernels())
    want = k_ops.parse_int_column(css, offs, lens, interpret=interpret_kernels())
    for f in ("value", "valid", "empty"):
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)))


def test_numparse_fused_issues_no_xla_gather():
    """Acceptance bar for the fusion: between the field index and type
    conversion the pallas backend issues no XLA-level take/gather — the
    fused kernels own the CSS indexing.  The unfused path is the positive
    control proving the detector sees the gather it is supposed to kill."""
    import jax
    from repro.core import ParserConfig, Schema, get_backend, make_csv_dfa

    be = get_backend("pallas")
    css = jnp.zeros(257, jnp.uint8)
    off = jnp.zeros(64, jnp.int32)
    ln = jnp.zeros(64, jnp.int32)
    schema = Schema.of(("i", "int32"), ("f", "float32"), ("d", "date"))

    # default config and an explicit window geometry, both windowed
    for window_rows in (0, 128):
        fused_cfg = ParserConfig(dfa=make_csv_dfa(), schema=schema,
                                 max_records=64, backend="pallas",
                                 fuse_typeconv=True, window_rows=window_rows)
        for dtype in ("int32", "float32", "date"):
            jx = jax.make_jaxpr(
                lambda c, o, l: be.parse_field[dtype](c, o, l, fused_cfg)
            )(css, off, ln)
            assert not _gathers_outside_pallas(jx.jaxpr), (window_rows, dtype)

    unfused_cfg = ParserConfig(dfa=make_csv_dfa(), schema=schema, max_records=64,
                               backend="pallas", fuse_typeconv=False)
    jx = jax.make_jaxpr(
        lambda c, o, l: be.parse_field["int32"](c, o, l, unfused_cfg)
    )(css, off, ln)
    assert _gathers_outside_pallas(jx.jaxpr)  # detector sanity check


# ---------------------------------------------------------------------------
# flashattn
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,sq,skv,d,causal,window", [
    (2, 4, 4, 256, 256, 64, True, None),
    (1, 8, 2, 128, 256, 64, False, None),   # GQA, cross-attn style
    (1, 4, 1, 256, 256, 32, True, 128),     # MQA + sliding window
    (2, 2, 2, 384, 384, 128, True, None),
])
def test_flashattn_vs_ref(rng, dtype, b, hq, hkv, sq, skv, d, causal, window):
    from repro.kernels.flashattn import ops as f_ops
    from repro.kernels.flashattn import ref as f_ref
    q = jnp.asarray(rng.normal(size=(b, hq, sq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, skv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, skv, d)), dtype)
    got = f_ops.flash_attention(q, k, v, causal=causal, window=window, block_q=128, block_kv=128,
                                interpret=interpret_kernels())
    want = f_ref.flash_attention(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


def test_flashattn_block_shape_sweep(rng):
    from repro.kernels.flashattn import ops as f_ops
    from repro.kernels.flashattn import ref as f_ref
    q = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    want = f_ref.flash_attention(q, k, v, causal=True)
    for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]:
        got = f_ops.flash_attention(q, k, v, causal=True, block_q=bq, block_kv=bk,
                                    interpret=interpret_kernels())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dfa_name", list(DFAS))
def test_dfa_scan_replay_fused_summaries(rng, dfa_name):
    """Fused replay+summary kernel == separate replay + chunk_summaries."""
    from repro.core import offsets as offs_mod
    from repro.core.transition import byte_groups, replay as jnp_replay
    from repro.kernels.dfa_scan import ops
    import jax.numpy as jnp

    dfa = DFAS[dfa_name]
    alphabet = np.frombuffer(b',"\n#xy z', np.uint8)
    chunks = jnp.asarray(
        alphabet[rng.integers(0, len(alphabet), size=256 * 32)].reshape(256, 32)
    )
    starts = jnp.zeros((256,), jnp.int32) + dfa.start_state

    cls_k, ends_k, summ = ops.replay_fused(chunks, starts, dfa, block_chunks=64,
                                           interpret=interpret_kernels())
    groups = byte_groups(chunks, dfa)
    cls_r, ends_r, _ = jnp_replay(groups, starts, dfa)
    np.testing.assert_array_equal(np.asarray(cls_k), np.asarray(cls_r))
    np.testing.assert_array_equal(np.asarray(ends_k), np.asarray(ends_r))

    ref = offs_mod.chunk_summaries(cls_r)
    np.testing.assert_array_equal(np.asarray(summ[:, 0]), np.asarray(ref.rec_count))
    np.testing.assert_array_equal(np.asarray(summ[:, 1]), np.asarray(ref.col_tag))
    np.testing.assert_array_equal(np.asarray(summ[:, 2]), np.asarray(ref.col_off))


@pytest.mark.parametrize("n_chunks", [96, 100])  # 100 % 32 != 0: pad path
def test_dfa_scan_parse_contexts(rng, n_chunks):
    """Fused §3.1+§3.2 entry == jnp pipeline + chunk_summaries, including
    chunk counts that do not divide block_chunks."""
    from repro.core import offsets as offs_mod
    from repro.core.transition import transition_pipeline
    from repro.kernels.dfa_scan import ops

    dfa = DFAS["csv"]
    alphabet = np.frombuffer(b',"\nabc', np.uint8)
    chunks = jnp.asarray(
        alphabet[rng.integers(0, len(alphabet), size=n_chunks * 32)]
        .reshape(n_chunks, 32)
    )
    cls_k, ends_k, summ = ops.parse_contexts(chunks, dfa, block_chunks=32,
                                             interpret=interpret_kernels())
    cls_j, ends_j, _ = transition_pipeline(chunks, dfa)
    np.testing.assert_array_equal(np.asarray(cls_k), np.asarray(cls_j))
    np.testing.assert_array_equal(np.asarray(ends_k), np.asarray(ends_j))
    ref = offs_mod.chunk_summaries(cls_j)
    np.testing.assert_array_equal(np.asarray(summ[:, 0]), np.asarray(ref.rec_count))
    np.testing.assert_array_equal(np.asarray(summ[:, 1]), np.asarray(ref.col_tag))
    np.testing.assert_array_equal(np.asarray(summ[:, 2]), np.asarray(ref.col_off))
