"""Blockwise online-softmax attention (FlashAttention-style) for TPU.

The model zoo's compute hot-spot: prefill at 32k context would materialise
(S × S) score matrices per head without it.  Grid is
``(batch, q_heads, q_blocks, kv_blocks)`` with the KV axis innermost;
running max / denominator / output accumulator live in VMEM scratch and are
finalised on the last KV block (the standard decomposition: Dao et al.,
arXiv:2205.14135, re-tiled for MXU-aligned 128-lane blocks).

GQA is handled in the index map — KV blocks are fetched from head
``q_head // group`` — so grouped KV is never materialised per q-head.
Supports causal masking and sliding windows (Hymba's local attention).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128
_NEG_INF = -1e30


def _make_kernel(block_q, block_kv, n_kv_blocks, scale, causal, window):
    def kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
        kv_idx = pl.program_id(3)
        q_idx = pl.program_id(2)

        @pl.when(kv_idx == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        q = q_ref[0, 0].astype(jnp.float32)  # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (BQ, BK)

        if causal or window is not None:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0
            )
            k_pos = kv_idx * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1
            )
            mask = jnp.ones((block_q, block_kv), jnp.bool_)
            if causal:
                mask &= q_pos >= k_pos
            if window is not None:
                mask &= (q_pos - k_pos) < window
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...][:, 0]           # (BQ,)
        l_prev = l_ref[...][:, 0]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        # Guard fully-masked rows (everything -inf) against NaNs.
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        alpha = jnp.where(m_prev <= _NEG_INF / 2, 0.0, alpha)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new[:, None]
        l_ref[...] = l_new[:, None]

        @pl.when(kv_idx == n_kv_blocks - 1)
        def _finalise():
            l = l_ref[...][:, 0]
            denom = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)

    return kernel


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool,
) -> jax.Array:
    """``q (B, Hq, Sq, D)``, ``k/v (B, Hkv, Skv, D)`` → ``(B, Hq, Sq, D)``.

    ``Hq`` must be a multiple of ``Hkv`` (GQA); sequence lengths must be
    multiples of the block sizes (callers pad — masked tail rows produce
    zeros, not NaNs).
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_kv, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    n_kv_blocks = skv // bk
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    kernel = _make_kernel(bq, bk, n_kv_blocks, scale, causal, window)
    return pl.pallas_call(
        kernel,
        grid=(b, hq, sq // bq, n_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j: (b_, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j: (b_, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
