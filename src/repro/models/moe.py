"""Mixture-of-experts FFN: two dispatch strategies.

``dense``  — GShard-style capacity dispatch via one-hot einsums.  Memory is
O(T·E·C) in the dispatch mask, fine for small expert counts (phi3.5's 16).

``a2a``    — expert parallelism for large E (kimi-k2's 384): a shard_map
region where tokens are routed, exchanged with a capacity-bounded
``all_to_all`` along the tensor-parallel axis, run through the local experts
with ``jax.lax.ragged_dot`` (grouped GEMM — the MegaBlocks-style path), and
returned by the inverse ``all_to_all``.  Expert weights are additionally
FSDP-sharded along the data axis and gathered at use (ZeRO-3), which is what
lets a 1T-param model's optimizer state fit the pod (DESIGN.md §4/§5).

Both paths drop overflow tokens against a capacity factor (the standard
trade; the router aux loss keeps load balanced) and add optional shared
experts (kimi) computed densely.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.models import layers as L


def init_moe(key, cfg):
    e, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    kr, k1, k2, k3, ks = jax.random.split(key, 5)
    p = {
        "router": L.init_linear(kr, d, e, jnp.float32),
        "w_gate": L.truncnorm(k1, (e, d, f), 1.0 / (d ** 0.5), cfg.param_dtype),
        "w_up": L.truncnorm(k2, (e, d, f), 1.0 / (d ** 0.5), cfg.param_dtype),
        "w_down": L.truncnorm(k3, (e, f, d), 1.0 / (f ** 0.5), cfg.param_dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.init_mlp(ks, d, cfg.moe_d_ff * cfg.n_shared_experts, cfg.param_dtype)
    return p


def moe_specs(cfg, tp="model", fsdp: Optional[str] = None):
    """Experts on tp; optionally FSDP-shard the d_model dim on the data axis."""
    p = {
        "router": L.linear_specs(None, None),
        "w_gate": P(tp, fsdp, None),
        "w_up": P(tp, fsdp, None),
        "w_down": P(tp, None, fsdp),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.mlp_specs(tp)
    return p


def _router(params, x2d, cfg):
    """Returns (weights (T, k) fp32, expert ids (T, k) int32, aux loss)."""
    logits = L.linear(params["router"], x2d.astype(jnp.float32))  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.top_k)
    if cfg.moe_renormalize:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # Switch-style load-balance loss: E · Σ_e f_e · p_e
    e = cfg.n_experts
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=1), axis=0
    )
    aux = e * jnp.sum(me * ce)
    return top_w, top_e, aux


# ---------------------------------------------------------------------------
# dense (one-hot) dispatch — small E
# ---------------------------------------------------------------------------

def moe_dense(params, x, cfg, sh):
    b, s, d = x.shape
    x2d = x.reshape(-1, d)
    t = x2d.shape[0]
    top_w, top_e, aux = _router(params, x2d, cfg)
    e = cfg.n_experts
    cap = int(max(1, (t * cfg.top_k * cfg.capacity_factor) // e))

    # position of each (token, slot) within its expert
    onehot = jax.nn.one_hot(top_e, e, dtype=jnp.int32)          # (T, k, E)
    flat = onehot.reshape(t * cfg.top_k, e)
    pos = jnp.cumsum(flat, axis=0) - flat                        # (T·k, E)
    slot = jnp.sum(pos * flat, axis=-1).reshape(t, cfg.top_k)    # (T, k)
    keep = slot < cap
    w = jnp.where(keep, top_w, 0.0)

    disp = (
        jax.nn.one_hot(top_e, e, dtype=x.dtype)[..., None]
        * jax.nn.one_hot(jnp.where(keep, slot, cap), cap + 1, dtype=x.dtype)[:, :, None, :]
    ).sum(1)[..., :cap]                                          # (T, E, C)
    comb = (
        jax.nn.one_hot(top_e, e, dtype=jnp.float32)[..., None]
        * jax.nn.one_hot(jnp.where(keep, slot, cap), cap + 1, dtype=jnp.float32)[:, :, None, :]
        * w[..., None, None]
    ).sum(1)[..., :cap]                                          # (T, E, C)

    xe = jnp.einsum("tec,td->ecd", disp, x2d)                    # (E, C, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, params["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"])         # (E, C, D)
    y = jnp.einsum("tec,ecd->td", comb, ye.astype(jnp.float32)).astype(x.dtype)

    if cfg.n_shared_experts:
        y = y + L.mlp(params["shared"], x2d)
    return y.reshape(b, s, d), aux


def moe_gather(params, x, cfg, sh):
    """Dropless per-token expert gather — the decode path.

    Decode batches are small, so gathering each token's top-k expert weights
    (the memory-bound regime MoE decode lives in anyway) is exact: no
    capacity, no dropped tokens, and decode logits match the teacher-forced
    forward pass bit-for-bit when the train path doesn't drop either.
    """
    b, s, d = x.shape
    x2d = x.reshape(-1, d)
    top_w, top_e, aux = _router(params, x2d, cfg)
    y = jnp.zeros_like(x2d, dtype=jnp.float32)
    for i in range(cfg.top_k):
        e = top_e[:, i]
        wg = params["w_gate"][e]          # (T, D, F) gather
        wu = params["w_up"][e]
        wd = params["w_down"][e]
        h = jax.nn.silu(jnp.einsum("td,tdf->tf", x2d, wg))
        h = h * jnp.einsum("td,tdf->tf", x2d, wu)
        y = y + top_w[:, i, None] * jnp.einsum("tf,tfd->td", h, wd).astype(jnp.float32)
    y = y.astype(x.dtype)
    if cfg.n_shared_experts:
        y = y + L.mlp(params["shared"], x2d)
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# all_to_all expert parallelism — large E
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _gather_int8(w, axis_name, shard_axis):
    """All-gather an FSDP weight shard in int8 + fp32 scales, dequantise to
    the original dtype.  Scales are per (expert, out-feature) over the
    sharded (d_model) axis, so each shard dequantises independently.

    Backward is the straight-through all-gather transpose: a (bf16)
    ``psum_scatter`` of the cotangent back onto the shard — quantisation is
    forward-only, so optimiser state stays exact (1-bit-Adam-style trade).
    """
    scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=shard_axis,
                    keepdims=True) / 127.0 + 1e-12
    wq = jnp.round(w.astype(jnp.float32) / scale).astype(jnp.int8)
    wq_g = jax.lax.all_gather(wq, axis_name, axis=0, tiled=False)
    sc_g = jax.lax.all_gather(scale.astype(jnp.float32), axis_name, axis=0,
                              tiled=False)
    w_g = (wq_g.astype(jnp.float32) * sc_g).astype(w.dtype)
    # (S, E, …) → concatenate the shards back along the sharded axis
    w_g = jnp.moveaxis(w_g, 0, shard_axis)       # (E, S, Dl, F) or (E, F, S, Dl)
    shp = list(w.shape)
    shp[shard_axis] = -1
    return w_g.reshape(shp)


def _gather_int8_fwd(w, axis_name, shard_axis):
    return _gather_int8(w, axis_name, shard_axis), w.shape


def _gather_int8_bwd(axis_name, shard_axis, shard_shape, g):
    gw = jax.lax.psum_scatter(g, axis_name, scatter_dimension=shard_axis,
                              tiled=True)
    return (gw.astype(jnp.float32).reshape(shard_shape),)


_gather_int8.defvjp(_gather_int8_fwd, _gather_int8_bwd)


def _moe_a2a_local(params, x_local, cfg, tp_axis, fsdp_axis):
    """Per-device body (inside shard_map).  ``x_local (T_l, D)``."""
    m = jax.lax.psum(1, tp_axis)                       # tp world size
    t_l, d = x_local.shape
    e = cfg.n_experts
    e_local = e // m
    k = cfg.top_k

    top_w, top_e, aux = _router(params, x_local, cfg)
    aux = jax.lax.pmean(aux, tp_axis)

    # ---- build send buffers: route (token, slot) pairs to owner ranks ----
    flat_e = top_e.reshape(-1)                          # (T_l·k,)
    flat_w = top_w.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t_l), k)
    dest = flat_e // e_local                            # owning tp rank
    cap = int(max(1, (t_l * k * cfg.capacity_factor) // m))

    oh = jax.nn.one_hot(dest, m, dtype=jnp.int32)       # (T_l·k, M)
    slot = (jnp.cumsum(oh, axis=0) - oh)
    slot = jnp.sum(slot * oh, axis=-1)                  # (T_l·k,)
    keep = slot < cap
    slot_c = jnp.where(keep, slot, cap)

    def scatter(src, fill, dtype):
        buf = jnp.full((m, cap + 1) + src.shape[1:], fill, dtype)
        return buf.at[dest, slot_c].set(src, mode="drop")[:, :cap]

    send_x = scatter(x_local[flat_t], 0, x_local.dtype)            # (M, C, D)
    send_e = scatter((flat_e % e_local).astype(jnp.int32), -1, jnp.int32)
    # ---- exchange along the tp axis --------------------------------------
    recv_x = jax.lax.all_to_all(send_x, tp_axis, 0, 0, tiled=False)
    recv_e = jax.lax.all_to_all(send_e, tp_axis, 0, 0, tiled=False)
    recv_x = recv_x.reshape(m * cap, d)
    recv_e = recv_e.reshape(m * cap)

    # ---- local experts: sort + grouped GEMM (ragged_dot) ------------------
    eid = jnp.where(recv_e < 0, e_local, recv_e)        # empty slots → pad group
    order = jnp.argsort(eid, stable=True)
    xs = recv_x[order]
    group_sizes = jnp.bincount(eid, length=e_local + 1)[:e_local].astype(jnp.int32)

    # FSDP: gather the d_model shards of this device's expert weights at use.
    wg, wu, wd = params["w_gate"], params["w_up"], params["w_down"]
    if fsdp_axis is not None:
        if cfg.moe_fsdp_int8:
            # int8-compressed weight gather (−50% AG bytes; per-(expert,
            # out-feature) scales, dequantised shard-wise after the gather —
            # EXPERIMENTS.md §Perf kimi iteration)
            wg = _gather_int8(wg, fsdp_axis, shard_axis=1)
            wu = _gather_int8(wu, fsdp_axis, shard_axis=1)
            wd = _gather_int8(wd, fsdp_axis, shard_axis=2)
        else:
            wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_axis, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_axis, axis=2, tiled=True)

    h = jax.nn.silu(jax.lax.ragged_dot(xs, wg, group_sizes))
    h = h * jax.lax.ragged_dot(xs, wu, group_sizes)
    ys = jax.lax.ragged_dot(h, wd, group_sizes)         # (M·C, D)

    inv = jnp.argsort(order)
    recv_y = ys[inv].reshape(m, cap, d)
    send_y = jax.lax.all_to_all(recv_y, tp_axis, 0, 0, tiled=False)  # back

    # ---- combine: weighted scatter-add back into token order -------------
    y_flat = send_y.reshape(m * cap, d)
    src_idx = dest * cap + slot_c                        # (T_l·k,) positions
    contrib = jnp.where(keep, flat_w, 0.0)[:, None] * y_flat[
        jnp.clip(src_idx, 0, m * cap - 1)
    ].astype(jnp.float32)
    y = jnp.zeros((t_l, d), jnp.float32).at[flat_t].add(contrib)
    return y.astype(x_local.dtype), aux


def moe_a2a(params, x, cfg, sh, mesh):
    """shard_map wrapper: tokens sharded over (dp…, tp), experts over tp."""
    b, s, d = x.shape
    tp = sh.tp
    fsdp = sh.dp[-1] if cfg.moe_fsdp else None
    p_x = P(sh.dp, tp, None)        # sequence-sharded over tp inside MoE
    in_specs = (
        {
            "router": {"w": P(None, None)},
            "w_gate": P(tp, fsdp, None),
            "w_up": P(tp, fsdp, None),
            "w_down": P(tp, None, fsdp),
            **({"shared": jax.tree.map(lambda _: P(None, None), params["shared"])}
               if "shared" in params else {}),
        },
        p_x,
    )

    def body(prm, xl):
        bl, sl, _ = xl.shape
        y, aux = _moe_a2a_local(
            {k: v for k, v in prm.items() if k != "shared"},
            xl.reshape(bl * sl, d), cfg, tp, fsdp,
        )
        if "shared" in prm:
            y = y + L.mlp(prm["shared"], xl.reshape(bl * sl, d))
        # aux is pmean'd over tp inside; also average over dp lanes
        return y.reshape(bl, sl, d), aux

    y, aux = shard_map(
        body, mesh=mesh, in_specs=in_specs,
        out_specs=(p_x, P()), check_vma=False,
    )(params, x)
    return y, aux


def moe_ffn(params, x, cfg, sh, mesh=None):
    if cfg.moe_impl == "a2a" and mesh is not None:
        return moe_a2a(params, x, cfg, sh, mesh)
    return moe_dense(params, x, cfg, sh)
