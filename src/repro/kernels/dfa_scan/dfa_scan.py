"""Pallas TPU kernels for ParPaRaw's per-chunk DFA simulation (paper §3.1).

Two kernels:

  * ``chunk_vectors_kernel`` — the |S|-simultaneous-DFA pass: every chunk
    folds its symbols into a state-transition vector.  Chunks ride the VPU
    lanes (``block_chunks`` per grid step); the state axis rides the
    sublanes.
  * ``replay_kernel`` — the second pass: one DFA per chunk from its true
    start state, emitting the symbol-class code stream.

TPU adaptation notes (DESIGN.md §3):
  * Symbol→group matching is branchless broadcast-compare against the DFA's
    distinguished bytes — the VPU-native analogue of the paper's SWAR
    LU-register trick.  No 256-entry LUT gather in the hot loop.
  * The state-transition table is applied via one-hot select chains
    (``Σ_g (g==g')·T[:,g']`` then ``Σ_s (v==s')·row[s']``): TPU vector lanes
    cannot dynamically index VMEM per-lane (the role MFIRA's BFI/BFE played
    on GPU), but |S|·|G| ≤ 64 makes select chains cheap and fully vector.
  * Chunks ride the lanes: the wrappers hand the kernels the byte matrix
    transposed, ``(K, C)``, so the symbol loop (a ``fori_loop`` over the
    chunk byte axis) reads byte ``k`` of every chunk in the block as one
    sublane row, ``ref[pl.ds(k, 1), :]`` — a ref-level load Mosaic lowers
    directly, VMEM-resident, no HBM traffic inside the loop.  The block is
    widened to int32 once per grid step into a VMEM scratch, because
    single-row dynamic loads are only lowered for 32-bit data.  Per-chunk
    outputs leave the same way (``(S, C)`` vectors, ``(K, C)`` classes,
    ``(1, C)`` end states, ``(3, C)`` summaries) and are transposed back by
    the wrappers.

Shape contract: ``chunks (C, K) uint8`` with C a multiple of
``block_chunks``; callers pad (identity vectors / PAD bytes are inert).
On a TPU the block must tile: ``block_chunks`` a multiple of 128 or all of
C.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.dfa import Dfa

DEFAULT_BLOCK_CHUNKS = 256


def _group_select(bytes_i32, group_bytes, n_groups):
    """Branchless group id for a vector of bytes (SWAR analogue).

    Shared with the whole-pipeline megakernel
    (``kernels/fused_pipeline``), whose in-kernel replay must classify
    bytes exactly like the staged replay kernels here.
    """
    g = jnp.full(bytes_i32.shape, n_groups - 1, jnp.int32)  # catch-all
    for gi, b in enumerate(group_bytes):
        g = jnp.where(bytes_i32 == b, gi, g)
    return g


def _widen(chunks_ref, scratch_ref):
    """Copy the ``(K, BC) uint8`` block into the int32 scratch once."""
    scratch_ref[...] = chunks_ref[...].astype(jnp.int32)


def _make_chunk_vectors_kernel(dfa: Dfa, block_chunks: int, chunk_bytes: int):
    S, G = dfa.n_states, dfa.n_groups
    group_bytes = dfa.group_bytes

    def kernel(chunks_ref, tt_ref, out_ref, data_ref):
        _widen(chunks_ref, data_ref)
        # T[:, g] as (S, 1) columns, VMEM-resident across the whole loop.
        tcols = [tt_ref[:, gi:gi + 1] for gi in range(G)]

        def body(k, vec):
            byte = data_ref[pl.ds(k, 1), :]                # (1, BC)
            g = _group_select(byte, group_bytes, G)
            # Tg[s', c] = T[s', g[c]]  via one-hot select over groups.
            tg = jnp.zeros((S, block_chunks), jnp.int32)
            for gi in range(G):
                tg = jnp.where(g == gi, tcols[gi], tg)
            # new_vec[s, c] = Tg[vec[s, c], c]  via one-hot select over states.
            new = jnp.zeros_like(vec)
            for si in range(S):
                new = jnp.where(vec == si, tg[si:si + 1, :], new)
            return new

        init = jax.lax.broadcasted_iota(jnp.int32, (S, block_chunks), 0)
        out_ref[...] = jax.lax.fori_loop(0, chunk_bytes, body, init)

    return kernel


def _block(c: int, block_chunks: int) -> int:
    bc = min(block_chunks, c)
    if c % bc:
        raise ValueError(f"n_chunks {c} not a multiple of block_chunks {bc}")
    return bc


def _scratch(k: int, bc: int):
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM((k, bc), jnp.int32)]


def chunk_vectors(
    chunks: jax.Array,
    dfa: Dfa,
    *,
    block_chunks: int = DEFAULT_BLOCK_CHUNKS,
    interpret: bool,
) -> jax.Array:
    """``(C, K) uint8`` → per-chunk state-transition vectors ``(C, S) int32``."""
    c, k = chunks.shape
    bc = _block(c, block_chunks)
    s = dfa.n_states
    kernel = _make_chunk_vectors_kernel(dfa, bc, k)
    tt = jnp.asarray(dfa.transition.astype(np.int32))
    vecs = pl.pallas_call(
        kernel,
        grid=(c // bc,),
        in_specs=[
            pl.BlockSpec((k, bc), lambda i: (0, i)),
            pl.BlockSpec((s, dfa.n_groups), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((s, bc), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((s, c), jnp.int32),
        scratch_shapes=_scratch(k, bc),
        interpret=interpret,
        name="dfa_chunk_vectors",
    )(chunks.T, tt)
    return vecs.T


def _replay_step(state, byte, group_bytes, n_groups, t_flat, e_flat):
    """One byte of the replay: ``(new_state, class)`` for a ``(1, BC)`` row."""
    g = _group_select(byte, group_bytes, n_groups)
    idx = state * n_groups + g  # in [0, S*G)
    new = jnp.zeros_like(state)
    cls = jnp.zeros_like(state)
    for j in range(len(t_flat)):
        hit = idx == j
        new = jnp.where(hit, t_flat[j], new)
        cls = jnp.where(hit, e_flat[j], cls)
    return new, cls


def _make_replay_kernel(dfa: Dfa, block_chunks: int, chunk_bytes: int,
                        summaries: bool):
    """Replay; with ``summaries`` it also accumulates the paper-§3.2
    per-chunk summaries (record count, abs/rel column offset) inside the
    same VMEM pass, so no separate jnp ``chunk_summaries`` pass over the
    class stream is needed.
    """
    from repro.core.dfa import FIELD_DELIM, RECORD_DELIM

    G = dfa.n_groups
    group_bytes = dfa.group_bytes
    t_flat = tuple(int(x) for x in dfa.transition.reshape(-1))
    e_flat = tuple(int(x) for x in dfa.emission.reshape(-1))

    def kernel(chunks_ref, start_ref, cls_ref, end_ref, *rest):
        summ_ref = rest[0] if summaries else None
        data_ref, cls_acc = rest[-2:]
        _widen(chunks_ref, data_ref)
        zeros = jnp.zeros((1, block_chunks), jnp.int32)

        def body(k, carry):
            state, rec_cnt, fld_since = carry
            new, cls = _replay_step(state, data_ref[pl.ds(k, 1), :],
                                    group_bytes, G, t_flat, e_flat)
            cls_acc[pl.ds(k, 1), :] = cls
            if summaries:
                is_rec = cls == RECORD_DELIM
                rec_cnt = rec_cnt + is_rec.astype(jnp.int32)
                # field delimiters since the last record delimiter (abs offset)
                fld_since = jnp.where(
                    is_rec, 0, fld_since + (cls == FIELD_DELIM).astype(jnp.int32))
            return new, rec_cnt, fld_since

        state, rec_cnt, fld_since = jax.lax.fori_loop(
            0, chunk_bytes, body, (start_ref[...], zeros, zeros)
        )
        cls_ref[...] = cls_acc[...].astype(cls_ref.dtype)
        end_ref[...] = state
        if summaries:
            # paper Fig. 4: ABS(=1) offset counts after the last record delim;
            # REL(=0) chunks report their total field-delim count — identical
            # here because fld_since never reset when has_rec is False.
            summ_ref[0:1, :] = rec_cnt
            summ_ref[1:2, :] = (rec_cnt > 0).astype(jnp.int32)
            summ_ref[2:3, :] = fld_since

    return kernel


def _replay_call(chunks, start_states, dfa, block_chunks, interpret,
                 summaries: bool):
    c, k = chunks.shape
    bc = _block(c, block_chunks)
    kernel = _make_replay_kernel(dfa, bc, k, summaries)
    col = lambda rows: pl.BlockSpec((rows, bc), lambda i: (0, i))
    out_specs = [col(k), col(1)]
    out_shape = [jax.ShapeDtypeStruct((k, c), jnp.uint8),
                 jax.ShapeDtypeStruct((1, c), jnp.int32)]
    if summaries:
        out_specs.append(col(3))
        out_shape.append(jax.ShapeDtypeStruct((3, c), jnp.int32))
    outs = pl.pallas_call(
        kernel,
        grid=(c // bc,),
        in_specs=[col(k), col(1)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=_scratch(k, bc) * 2,
        interpret=interpret,
        name="dfa_replay",
    )(chunks.T, start_states.astype(jnp.int32)[None, :])
    return (outs[0].T, outs[1][0]) + tuple(o.T for o in outs[2:])


def replay_fused(
    chunks: jax.Array,
    start_states: jax.Array,
    dfa: Dfa,
    *,
    block_chunks: int = DEFAULT_BLOCK_CHUNKS,
    interpret: bool,
):
    """Fused replay: ``(C,K) bytes + (C,) starts → (classes (C,K) uint8,
    end states (C,), summaries (C,3) int32 [rec_count, col_tag, col_off])``.
    """
    return _replay_call(chunks, start_states, dfa, block_chunks, interpret,
                        summaries=True)


def replay(
    chunks: jax.Array,
    start_states: jax.Array,
    dfa: Dfa,
    *,
    block_chunks: int = DEFAULT_BLOCK_CHUNKS,
    interpret: bool,
):
    """Replay pass: ``(C, K) bytes + (C,) start states → (C, K) classes,
    (C,) end states``."""
    return _replay_call(chunks, start_states, dfa, block_chunks, interpret,
                        summaries=False)
