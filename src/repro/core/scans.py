"""Prefix scans that compile in bounded time for the TPU.

XLA lowers ``jnp.cumsum`` and ``lax.cummax`` on the TPU to reduce-window
programs, and a long ``lax.associative_scan`` to a deep slice-and-pad
tree, and both compile in time that grows with the scanned length.
Compiling for a v5e on an 8-core x86 host, a ``(16384, 64)`` cumsum along
its 64-long axis took 12 s, a flat 1 MiB one 30 s, and the §3.1
composite scan over 65,536 chunks 34 s — several of them sit in every
parse step.  A log-depth
associative scan over a short axis compiles in about a second, so
:func:`scan` runs one over blocks of :data:`BLOCK` elements and recurses
on the block totals.  Integer results are the same bits as
``jnp.cumsum`` / ``lax.cummax``; any associative ``op`` works.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: Scan length handled by one associative scan.
BLOCK = 128


def scan(op, x: jax.Array, identity: jax.Array) -> jax.Array:
    """Inclusive scan of ``op`` along axis 0 of ``x``.

    Element ``i`` is ``x[i]`` (shape ``x.shape[1:]``); ``identity`` is the
    element ``op`` leaves unchanged, broadcastable to that shape, and
    ``op`` combines same-shaped stacks of elements elementwise, as
    ``lax.associative_scan`` requires.
    """
    n = x.shape[0]
    if n <= BLOCK:
        return jax.lax.associative_scan(op, x, axis=0)
    nb = -(-n // BLOCK)
    elem = x.shape[1:]
    pad = jnp.broadcast_to(jnp.asarray(identity, x.dtype),
                           (nb * BLOCK - n,) + elem)
    inner = jax.lax.associative_scan(
        op, jnp.concatenate([x, pad]).reshape((nb, BLOCK) + elem), axis=1)
    carry = scan(op, inner[:, -1], identity)               # per block
    first = jnp.broadcast_to(jnp.asarray(identity, x.dtype), (1,) + elem)
    carry = jnp.concatenate([first, carry[:-1]])           # exclusive
    out = op(jnp.broadcast_to(carry[:, None], inner.shape), inner)
    return out.reshape((nb * BLOCK,) + elem)[:n]


def _along(op, x, axis, identity):
    return jnp.moveaxis(scan(op, jnp.moveaxis(x, axis, 0), identity), 0, axis)


def cumsum(x: jax.Array, axis: int = 0) -> jax.Array:
    """``jnp.cumsum`` for integer ``x``."""
    return _along(jnp.add, x, axis, 0)


def cummax(x: jax.Array, axis: int = 0) -> jax.Array:
    """``lax.cummax`` for integer ``x``."""
    return _along(jnp.maximum, x, axis, jnp.iinfo(x.dtype).min)
