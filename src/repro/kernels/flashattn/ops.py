"""Jit'd wrapper for the flash attention kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flashattn import flashattn


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_kv", "interpret"),
)
def flash_attention(q, k, v, causal: bool = True, window=None, scale=None,
                    block_q: int = flashattn.DEFAULT_BLOCK_Q,
                    block_kv: int = flashattn.DEFAULT_BLOCK_KV,
                    *, interpret: bool):
    return flashattn.flash_attention(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
    )
