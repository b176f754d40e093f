"""State-transition vectors and their associative composite (paper §3.1).

A chunk's *state-transition vector* ``v`` satisfies ``v[s] = final state of a
DFA that entered the chunk in state s``.  The composite

    (a ∘ b)[s] = b[a[s]]

is associative, so an exclusive ``associative_scan`` over per-chunk vectors
yields every chunk's true start state with O(log n_chunks) depth and zero
sequential work — the paper's core contribution.

Two interchangeable composite implementations are provided:

  * ``compose`` — gather form ``take_along_axis(b, a)``; O(S) work per pair,
    runs on the TPU VPU.
  * ``compose_matmul`` — one-hot boolean-matrix product; O(S²) MACs per pair
    but lands on the MXU.  ``M[i, j] = 1 iff v[i] == j`` and function
    composition "apply a, then b" is exactly ``A @ B``.

Which one wins is workload/hardware dependent; ``benchmarks/bench_scan.py``
measures both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scans
from repro.core.dfa import Dfa


def byte_groups(raw: jax.Array, dfa: Dfa) -> jax.Array:
    """Map raw bytes ``(…,) uint8`` to symbol groups via the 256-entry LUT.

    jnp reference path; the Pallas kernel replaces this with broadcast
    compares against ``dfa.group_bytes`` (TPU analogue of the paper's SWAR
    matching, see kernels/dfa_scan).
    """
    lut = jnp.asarray(dfa.group_of)
    return lut[raw.astype(jnp.int32)]


def identity_vector(n_states: int, dtype=jnp.int32) -> jax.Array:
    return jnp.arange(n_states, dtype=dtype)


def compose(a: jax.Array, b: jax.Array) -> jax.Array:
    """Composite of state-transition vectors: ``(a ∘ b)[s] = b[a[s]]``.

    Shapes ``(..., S)``; leading dims broadcast elementwise (as required by
    ``lax.associative_scan``).  A select over the |S| states rather than a
    gather: elementwise work that XLA compiles quickly for the TPU at any
    chunk count, where the gather form took 30 s for 65,536 chunks
    (compiled on an 8-core x86 host).
    """
    a = a.astype(jnp.int32)
    out = jnp.zeros(jnp.broadcast_shapes(a.shape, b.shape), b.dtype)
    for t in range(b.shape[-1]):
        out = jnp.where(a == t, b[..., t:t + 1], out)
    return out


def vectors_to_matrices(vecs: jax.Array, n_states: int, dtype=jnp.float32) -> jax.Array:
    """One-hot encode ``(..., S)`` vectors into ``(..., S, S)`` matrices."""
    return jax.nn.one_hot(vecs, n_states, dtype=dtype)


def matrices_to_vectors(mats: jax.Array) -> jax.Array:
    """Invert ``vectors_to_matrices`` (rows are one-hot)."""
    n = mats.shape[-1]
    return (mats @ jnp.arange(n, dtype=mats.dtype)).astype(jnp.int32)


def compose_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """MXU form of the composite: one-hot ``A @ B``."""
    return jnp.matmul(a, b)


def chunk_transition_vectors(groups: jax.Array, dfa: Dfa) -> jax.Array:
    """Per-chunk state-transition vectors.

    Args:
      groups: ``(n_chunks, chunk_bytes) int32`` symbol groups.
    Returns:
      ``(n_chunks, n_states) int32`` vectors — the |S| simultaneous DFA
      instances of paper §3.1, vectorised across chunks instead of across
      GPU threads.
    """
    n_chunks = groups.shape[0]
    s = dfa.n_states
    t_flat = jnp.asarray(dfa.transition.reshape(-1).astype(np.int32))
    n_groups = dfa.n_groups

    def step(vec, g_col):
        # vec: (n_chunks, S); g_col: (n_chunks,)
        new = t_flat[vec * n_groups + g_col[:, None]]
        return new, None

    init = jnp.broadcast_to(identity_vector(s), (n_chunks, s))
    vec, _ = jax.lax.scan(step, init, groups.T)
    return vec


def exclusive_scan_vectors(vecs: jax.Array, use_matmul: bool = False) -> jax.Array:
    """Exclusive composite scan over chunk vectors ``(n_chunks, S)``.

    Row ``i`` of the result maps "state the sequential DFA was in at the start
    of the input" → "state at the start of chunk i" (paper Fig. 3).
    """
    n_states = vecs.shape[-1]
    if use_matmul:
        mats = vectors_to_matrices(vecs, n_states)
        inc = jax.lax.associative_scan(compose_matmul, mats, axis=0)
        inc = matrices_to_vectors(inc)
    else:
        inc = scans.scan(compose, vecs, identity_vector(n_states))
    ident = jnp.broadcast_to(identity_vector(n_states), (1, n_states))
    return jnp.concatenate([ident, inc[:-1]], axis=0)


def fold_vectors(vecs: jax.Array) -> jax.Array:
    """Composite-reduce ``(n_chunks, S) → (S,)`` (log-depth tree).

    Used by the distributed parser to summarise a device shard before the
    cross-device scan.
    """
    n = vecs.shape[0]
    # Pad to a power of two with identity vectors, then tree-reduce.
    n_pad = 1 << max(1, (n - 1).bit_length())
    ident = jnp.broadcast_to(identity_vector(vecs.shape[-1]), (n_pad - n, vecs.shape[-1]))
    v = jnp.concatenate([vecs, ident], axis=0)
    while v.shape[0] > 1:
        v = compose(v[0::2], v[1::2])
    return v[0]


def start_states(scanned: jax.Array, dfa: Dfa, initial_state: jax.Array | None = None) -> jax.Array:
    """Read each chunk's true start state out of the scanned vectors.

    ``initial_state`` overrides the DFA's start state — used by the streaming
    parser, which threads the previous partition's end state through
    (paper §4.4 carry-over).
    """
    if initial_state is None:
        initial_state = jnp.int32(dfa.start_state)
    return jnp.take_along_axis(
        scanned, jnp.broadcast_to(initial_state, (scanned.shape[0], 1)).astype(jnp.int32), axis=1
    )[:, 0]


def replay(
    groups: jax.Array,
    start: jax.Array,
    dfa: Dfa,
):
    """Second pass (paper §3.1 end): re-simulate one DFA instance per chunk
    from its now-known start state, emitting the symbol-class code stream.

    Args:
      groups: ``(n_chunks, chunk_bytes) int32``.
      start:  ``(n_chunks,) int32`` true start states.
    Returns:
      classes: ``(n_chunks, chunk_bytes) uint8`` symbol classes.
      states:  ``(n_chunks,) int32`` end state per chunk.
      saw_invalid: ``(n_chunks,) bool`` — whether the invalid sink was hit.
    """
    t_flat = jnp.asarray(dfa.transition.reshape(-1).astype(np.int32))
    e_flat = jnp.asarray(dfa.emission.reshape(-1).astype(np.int32))
    n_groups = dfa.n_groups
    inv = dfa.invalid_state

    def step(state, g_col):
        idx = state * n_groups + g_col
        cls = e_flat[idx]
        new = t_flat[idx]
        return new, cls

    final, classes = jax.lax.scan(step, start.astype(jnp.int32), groups.T)
    classes = classes.T.astype(jnp.uint8)
    if inv is None:
        saw_invalid = jnp.zeros(final.shape, bool)
    else:
        # The sink is absorbing, so "ever hit" == "ended there".
        saw_invalid = final == inv
    return classes, final, saw_invalid


@functools.partial(jax.jit, static_argnames=("dfa", "use_matmul"))
def transition_pipeline(raw_chunks: jax.Array, dfa: Dfa, use_matmul: bool = False):
    """Fused convenience entry: bytes → (classes, end_states, saw_invalid).

    ``raw_chunks``: ``(n_chunks, chunk_bytes) uint8``.
    """
    groups = byte_groups(raw_chunks, dfa)
    vecs = chunk_transition_vectors(groups, dfa)
    scanned = exclusive_scan_vectors(vecs, use_matmul=use_matmul)
    start = start_states(scanned, dfa)
    return replay(groups, start, dfa)


def sequential_reference(raw: np.ndarray, dfa: Dfa):
    """Pure-numpy sequential oracle: exactly what a one-thread parser does.

    Used by tests to validate the parallel pipeline symbol-for-symbol.
    """
    state = dfa.start_state
    classes = np.zeros(raw.shape[0], np.uint8)
    states = np.zeros(raw.shape[0], np.int32)
    for i, b in enumerate(raw):
        g = dfa.group_of[b]
        states[i] = state
        classes[i] = dfa.emission[state, g]
        state = dfa.transition[state, g]
    return classes, states, state
