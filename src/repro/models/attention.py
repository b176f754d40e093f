"""Grouped-query attention: training (dense / chunked-flash), prefill, and
KV-cache decode — plus the mesh-wide distributed decode combine.

Implementations:
  * ``dense``   — materialised scores; fine up to a few k context.
  * ``chunked`` — pure-jnp blockwise online softmax (lax.scan over KV
    blocks); O(S·B) memory, lowers on any backend — the dry-run path for the
    32k shapes.
  * ``pallas``  — the kernels/flashattn TPU kernel (interpret-validated).

Decode uses a ring-buffer-free static KV cache with ``dynamic_update_slice``
and position masking; sliding-window archs (Hymba) keep a rolling window
cache instead, bounding memory for the 500k shapes.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L

_NEG = -1e30


def _largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of n that is ≤ cap (chunked attention block pick —
    handles odd totals like 32768 tokens + 256 VLM patches)."""
    c = min(cap, n)
    while n % c:
        c -= 1
    return c


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attention(key, cfg):
    hd = cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": L.init_linear(k1, cfg.d_model, cfg.n_heads * hd, cfg.param_dtype, bias=cfg.qkv_bias),
        "wk": L.init_linear(k2, cfg.d_model, cfg.n_kv_heads * hd, cfg.param_dtype, bias=cfg.qkv_bias),
        "wv": L.init_linear(k3, cfg.d_model, cfg.n_kv_heads * hd, cfg.param_dtype, bias=cfg.qkv_bias),
        "wo": L.init_linear(k4, cfg.n_heads * hd, cfg.d_model, cfg.param_dtype),
    }


def attention_specs(cfg, tp="model"):
    return {
        "wq": L.linear_specs(None, tp, bias=cfg.qkv_bias),
        "wk": L.linear_specs(None, tp, bias=cfg.qkv_bias),
        "wv": L.linear_specs(None, tp, bias=cfg.qkv_bias),
        "wo": L.linear_specs(tp, None),
    }


# ---------------------------------------------------------------------------
# Score computation
# ---------------------------------------------------------------------------

def _dense_attn(q, k, v, causal, window, q_offset=0):
    """q (B,Sq,H,hd), k/v (B,Skv,KV,hd) → (B,Sq,H,hd)."""
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    group = h // kv
    qg = q.reshape(b, sq, kv, group, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32))
    s *= 1.0 / math.sqrt(hd)
    q_pos = jnp.arange(sq)[:, None] + q_offset
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask[None, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, h, hd).astype(q.dtype)


def _chunked_attn(q, k, v, causal, window, chunk_q=512, chunk_kv=1024):
    """Blockwise online-softmax in pure jnp (flash decomposition).

    Memory O(chunk_q · chunk_kv) per (batch, head) instead of O(S²); the
    sequential KV loop is a ``lax.scan`` so the HLO stays depth-1.
    """
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    group = h // kv
    cq = _largest_divisor(sq, chunk_q)
    ck = _largest_divisor(skv, chunk_kv)
    nq, nk = sq // cq, skv // ck
    scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(b, nq, cq, kv, group, hd)
    kc = k.reshape(b, nk, ck, kv, hd)
    vc = v.reshape(b, nk, ck, kv, hd)

    def q_block(qi, q_blk):
        # q_blk (b, cq, kv, group, hd)
        def kv_step(carry, inp):
            m_prev, l_prev, acc = carry
            kj, (k_blk, v_blk) = inp
            s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk.astype(jnp.float32),
                           k_blk.astype(jnp.float32)) * scale
            q_pos = qi * cq + jnp.arange(cq)[:, None]
            k_pos = kj * ck + jnp.arange(ck)[None, :]
            mask = jnp.ones((cq, ck), bool)
            if causal:
                mask &= q_pos >= k_pos
            if window is not None:
                mask &= (q_pos - k_pos) < window
            s = jnp.where(mask[None, None, None], s, _NEG)
            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(s <= _NEG / 2, 0.0, p)
            alpha = jnp.exp(m_prev - m_new)
            alpha = jnp.where(m_prev <= _NEG / 2, 0.0, alpha)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p, v_blk.astype(jnp.float32))
            acc = acc * alpha[..., None] + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, kv, group, cq), _NEG, jnp.float32)
        l0 = jnp.zeros((b, kv, group, cq), jnp.float32)
        a0 = jnp.zeros((b, kv, group, cq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk), (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0))),
        )
        denom = jnp.where(l == 0.0, 1.0, l)
        o = (acc / denom[..., None])           # (b, kv, group, cq, hd)
        return jnp.moveaxis(o, 3, 1).reshape(b, cq, kv * group, hd)

    outs = jax.lax.map(lambda args: q_block(*args),
                       (jnp.arange(nq), jnp.moveaxis(qg, 1, 0)))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, hd).astype(q.dtype)


def multihead_attention(q, k, v, *, causal=True, window=None, impl="dense",
                        q_offset=0, chunk_q=512, chunk_kv=1024):
    if impl == "chunked":
        return _chunked_attn(q, k, v, causal, window, chunk_q, chunk_kv)
    if impl == "pallas":
        from repro.core.backends import interpret_kernels
        from repro.kernels.flashattn import flash_attention
        o = flash_attention(
            jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1),
            causal=causal, window=window, interpret=interpret_kernels(),
        )
        return jnp.moveaxis(o, 1, 2)
    return _dense_attn(q, k, v, causal, window, q_offset)


# ---------------------------------------------------------------------------
# Block-level forward (projections + RoPE + attention)
# ---------------------------------------------------------------------------

def attend(params, x, cfg, sh, *, kv_x=None, causal=True, window=None,
           positions=None, impl=None):
    """Full attention sub-layer.  ``kv_x`` enables cross-attention."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    kv_x = x if kv_x is None else kv_x
    skv = kv_x.shape[1]
    # TP constraints go on the *flat* head dims (H·hd always divides the TP
    # axis; raw KV head counts like 5 or 8 do not — DESIGN.md §4)
    q = sh.act(L.linear(params["wq"], x), sh.dp, None, sh.tp)
    k = sh.act(L.linear(params["wk"], kv_x), sh.dp, None, sh.tp)
    v = sh.act(L.linear(params["wv"], kv_x), sh.dp, None, sh.tp)
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, skv, cfg.n_kv_heads, hd)
    v = v.reshape(b, skv, cfg.n_kv_heads, hd)
    if cfg.rope_theta and kv_x is x:
        pos = positions if positions is not None else jnp.arange(s)
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k = L.apply_rope(k, pos, cfg.rope_theta)
    impl = impl or ("chunked" if s >= cfg.attn_chunk_threshold else "dense")
    o = multihead_attention(q, k, v, causal=causal, window=window, impl=impl,
                            chunk_q=cfg.attn_chunk_q, chunk_kv=cfg.attn_chunk_kv)
    o = sh.bshd(o)
    return L.linear(params["wo"], o.reshape(b, s, cfg.n_heads * hd))


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jax.Array        # (B, S_max, KV, hd)
    v: jax.Array        # (B, S_max, KV, hd)
    length: jax.Array   # () int32 — tokens currently cached


def init_kv_cache(batch, max_seq, n_kv, head_dim, dtype):
    return KVCache(
        k=jnp.zeros((batch, max_seq, n_kv, head_dim), dtype),
        v=jnp.zeros((batch, max_seq, n_kv, head_dim), dtype),
        length=jnp.zeros((), jnp.int32),
    )


def kv_cache_specs(sh, seq_axis=None):
    from jax.sharding import PartitionSpec as P
    return KVCache(
        k=P(sh.dp, seq_axis, sh.tp, None),
        v=P(sh.dp, seq_axis, sh.tp, None),
        length=P(),
    )


def decode_attend(params, x, cache: KVCache, cfg, sh, *, window=None):
    """One-token decode step: update cache, attend against it.

    ``x (B, 1, D)``; ``cache.length (B,)`` carries *per-slot* positions so
    the serving engine's continuous batching can mix requests at different
    depths in one decode batch.  Returns (out, new_cache); the caller owns
    the length increment (it may mask inactive slots).
    """
    b = x.shape[0]
    hd = cfg.head_dim
    pos = cache.length  # (B,)
    q = L.linear(params["wq"], x).reshape(b, 1, cfg.n_heads, hd)
    k = L.linear(params["wk"], x).reshape(b, 1, cfg.n_kv_heads, hd)
    v = L.linear(params["wv"], x).reshape(b, 1, cfg.n_kv_heads, hd)
    if cfg.rope_theta:
        q = L.apply_rope(q, pos[:, None], cfg.rope_theta)
        k = L.apply_rope(k, pos[:, None], cfg.rope_theta)

    upd = jax.vmap(
        lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (p, 0, 0))
    )
    new_k = upd(cache.k, k.astype(cache.k.dtype), pos)
    new_v = upd(cache.v, v.astype(cache.v.dtype), pos)

    group = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(b, cfg.n_kv_heads, group, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                   new_k.astype(jnp.float32)) / math.sqrt(hd)
    k_pos = jnp.arange(new_k.shape[1])
    mask = k_pos[None, :] <= pos[:, None]                      # (B, S)
    if window is not None:
        mask &= k_pos[None, :] > (pos[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, new_v.astype(jnp.float32))
    o = o.reshape(b, 1, cfg.n_heads * hd).astype(x.dtype)
    out = L.linear(params["wo"], o)
    return out, KVCache(new_k, new_v, pos)


def distributed_decode_combine(partial_max, partial_sumexp, partial_pv, axis):
    """Flash-decoding across the mesh: each shard attends over its slice of a
    sequence-sharded KV cache; this combines the per-shard (m, l, Σp·v)
    triples into exact softmax attention with two tiny collectives."""
    m_glob = jax.lax.pmax(partial_max, axis)
    scale = jnp.exp(partial_max - m_glob)
    l_glob = jax.lax.psum(partial_sumexp * scale, axis)
    pv_glob = jax.lax.psum(partial_pv * scale[..., None], axis)
    return pv_glob / jnp.where(l_glob == 0, 1.0, l_glob)[..., None]
