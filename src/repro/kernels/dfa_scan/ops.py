"""Jit'd public wrappers for the dfa_scan kernels.

``parse_classes`` is the kernel-backed equivalent of
``repro.core.transition.transition_pipeline``: Pallas kernels for the two
chunk-local passes, XLA ``associative_scan`` for the O(C·S) composite scan
between them (the scan is bandwidth-trivial next to the byte passes and XLA
already emits a work-efficient tree for it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import transition as tr
from repro.core.dfa import Dfa
from repro.kernels.dfa_scan import dfa_scan


@functools.partial(
    jax.jit, static_argnames=("dfa", "block_chunks", "interpret")
)
def chunk_vectors(chunks, dfa: Dfa, block_chunks: int = dfa_scan.DEFAULT_BLOCK_CHUNKS,
                  *, interpret: bool):
    return dfa_scan.chunk_vectors(chunks, dfa, block_chunks=block_chunks,
                                  interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("dfa", "block_chunks", "interpret")
)
def replay(chunks, start_states, dfa: Dfa,
           block_chunks: int = dfa_scan.DEFAULT_BLOCK_CHUNKS,
           *, interpret: bool):
    return dfa_scan.replay(chunks, start_states, dfa,
                           block_chunks=block_chunks, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("dfa", "block_chunks", "interpret", "use_matmul")
)
def parse_classes(chunks, dfa: Dfa,
                  block_chunks: int = dfa_scan.DEFAULT_BLOCK_CHUNKS,
                  *, interpret: bool, use_matmul: bool = False):
    """Kernel-backed context determination + replay (paper §3.1 end to end)."""
    vecs = dfa_scan.chunk_vectors(chunks, dfa, block_chunks=block_chunks,
                                  interpret=interpret)
    scanned = tr.exclusive_scan_vectors(vecs, use_matmul=use_matmul)
    start = tr.start_states(scanned, dfa)
    classes, ends = dfa_scan.replay(chunks, start, dfa,
                                    block_chunks=block_chunks,
                                    interpret=interpret)
    return classes, ends


@functools.partial(
    jax.jit, static_argnames=("dfa", "block_chunks", "interpret")
)
def replay_fused(chunks, start_states, dfa: Dfa,
                 block_chunks: int = dfa_scan.DEFAULT_BLOCK_CHUNKS,
                 *, interpret: bool):
    """Fused replay + paper-§3.2 chunk summaries in one VMEM pass."""
    return dfa_scan.replay_fused(chunks, start_states, dfa,
                                 block_chunks=block_chunks,
                                 interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("dfa", "block_chunks", "interpret", "use_matmul")
)
def parse_contexts(chunks, dfa: Dfa,
                   block_chunks: int = dfa_scan.DEFAULT_BLOCK_CHUNKS,
                   *, interpret: bool, use_matmul: bool = False,
                   initial_state=None):
    """Kernel-backed §3.1 + fused §3.2: context determination, replay, and
    per-chunk offset summaries — ``parse_classes`` upgraded to the fused
    replay so the downstream record/column scan consumes kernel-produced
    summaries with no separate jnp ``chunk_summaries`` pass.  Chunk counts
    that do not divide ``block_chunks`` are padded with inert PAD chunks and
    sliced back (same contract as ``backend="pallas"``).

    Returns ``(classes (C,K) uint8, end_states (C,) int32,
    summaries (C,3) int32 [rec_count, col_tag, col_off])``.
    """
    from repro.core.backends import pad_to_block
    from repro.core.dfa import PAD_BYTE

    bc = min(block_chunks, chunks.shape[0])
    padded, n = pad_to_block(chunks, bc, PAD_BYTE)
    vecs = dfa_scan.chunk_vectors(padded, dfa, block_chunks=bc,
                                  interpret=interpret)[:n]
    scanned = tr.exclusive_scan_vectors(vecs, use_matmul=use_matmul)
    start = tr.start_states(scanned, dfa, initial_state=initial_state)
    start_p, _ = pad_to_block(start, bc, dfa.start_state)
    classes, ends, summ = dfa_scan.replay_fused(
        padded, start_p, dfa, block_chunks=bc, interpret=interpret
    )
    return classes[:n], ends[:n], summ[:n]
