"""Mixture-of-Experts layer with expert parallelism (kimi-k2, olmoe).

Design (see DESIGN.md §5):
* Experts are sharded over the ``model`` mesh axis (EP): kimi 384/16 = 24,
  olmoe 64/16 = 4 experts per shard. Expert weights are additionally
  FSDP-sharded over ``data``(+``pod``) on the d_model dim; the gather back to
  full d_model happens at the shard_map boundary (GSPMD all-gather).
* Train/prefill ("sp" path): the residual stream is sequence-sharded over
  ``model``; each model rank routes its local tokens and exchanges them with
  the expert-owning ranks via a capacity-bounded ``all_to_all`` (GShard
  style), computes its local experts' GEMMs, and reverses the exchange.
  No dispatch one-hot einsums — routing is sorts/gathers/scatters, so HLO
  FLOPs stay honest (the GShard (T,E,C) dispatch einsum would dwarf the
  expert GEMMs by ~100x in compiled FLOPs).
* Decode ("replicated" path): tokens are replicated over ``model``; each rank
  computes only its local experts' contributions and psums. For the tiny
  per-step token counts of decoding this costs one small all-reduce.

The per-expert batched GEMM is the Pallas ``moe_gmm`` kernel's target shape;
the XLA path uses a plain batched einsum over the capacity buffer.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.transformer import Ctx, attn_defs, attn_apply, _norm
from repro.sharding.partition import constrain


def moe_mlp_defs(cfg: ModelConfig,
                 weight_stationary: bool = False) -> Dict[str, L.ParamDef]:
    assert cfg.moe is not None
    D, F, E = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    # weight-stationary (inference): shard the per-expert d_ff over `data`
    # ("expert_mlp" rule) instead of FSDP-sharding d_model — weights never
    # move; activations (tiny at decode) do.
    d_lg = None if weight_stationary else "embed"
    f_lg = "expert_mlp" if weight_stationary else None
    return {
        "ln": L.ParamDef((D,), ("embed",), "ones"),
        "router": L.ParamDef((D, E), (None, None)),
        "wg": L.ParamDef((E, D, F), ("experts", d_lg, f_lg)),
        "wu": L.ParamDef((E, D, F), ("experts", d_lg, f_lg)),
        "wd": L.ParamDef((E, F, D), ("experts", f_lg, d_lg)),
    }


def moe_block_defs(cfg: ModelConfig, weight_stationary: bool = False
                   ) -> Dict[str, Any]:
    return {"attn": attn_defs(cfg),
            "moe": moe_mlp_defs(cfg, weight_stationary)}


# --------------------------------------------------------------------------
# Routing helpers (local, static shapes)
# --------------------------------------------------------------------------
def _topk_route(x, w_router, top_k: int):
    """x: (T, D) -> (weights (T,k) f32, experts (T,k) i32, probs (T,E) f32)."""
    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topw, tope = jax.lax.top_k(probs, top_k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    return topw, tope.astype(jnp.int32), probs


def _positions_in_expert(flat_e, n_experts: int):
    """Rank of each (token,k) pair within its expert (by stable sort)."""
    tk = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    run_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
    ranks_sorted = jnp.arange(tk, dtype=jnp.int32) - run_start.astype(jnp.int32)
    ranks = jnp.zeros((tk,), jnp.int32).at[order].set(ranks_sorted)
    return ranks


def aux_losses(probs, tope, n_experts: int) -> Dict[str, jnp.ndarray]:
    """Switch-style load-balancing loss + router z-loss (local shard values)."""
    T = probs.shape[0]
    k = tope.shape[-1]
    counts = jnp.zeros((n_experts,), jnp.float32).at[tope.reshape(-1)].add(1.0)
    frac_tokens = counts / jnp.maximum(T * k, 1)
    frac_probs = probs.mean(axis=0)
    lb = n_experts * jnp.sum(frac_tokens * frac_probs)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(
        jnp.log(jnp.maximum(probs, 1e-20)), axis=-1)))
    return {"load_balance": lb, "router_z": z}


def _expert_ffn(wg, wu, wd, xs):
    """xs: (E_loc, C, D) -> (E_loc, C, D); SwiGLU per expert (gmm target)."""
    g = jnp.einsum("ecd,edf->ecf", xs, wg)
    u = jnp.einsum("ecd,edf->ecf", xs, wu)
    h = L.swiglu(g, u)
    return jnp.einsum("ecf,efd->ecd", h, wd)


# --------------------------------------------------------------------------
# SP + all-to-all path (train / prefill)
# --------------------------------------------------------------------------
def _moe_local_a2a(cfg: ModelConfig, model_axis: str, n_ranks: int,
                   x_loc, w_router, wg, wu, wd):
    """Per-device body under shard_map. x_loc: (B_loc, S_loc, D)."""
    moe = cfg.moe
    E = moe.n_experts
    e_loc = E // n_ranks
    B, S, D = x_loc.shape
    T = B * S
    xt = x_loc.reshape(T, D)

    topw, tope, probs = _topk_route(xt, w_router, moe.top_k)
    aux = aux_losses(probs, tope, E)

    flat_e = tope.reshape(-1)                     # (T*k,)
    flat_w = topw.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), moe.top_k)
    ranks = _positions_in_expert(flat_e, E)

    cap = int(np.ceil(T * moe.top_k / E * moe.capacity_factor))
    cap = max(8, int(np.ceil(cap / 8) * 8))       # pad for lane alignment
    valid = ranks < cap
    slot = flat_e * cap + jnp.where(valid, ranks, 0)

    # dispatch into (E, cap, D) send buffer
    src = jnp.where(valid[:, None], xt[flat_t], 0).astype(xt.dtype)
    buf = jnp.zeros((E * cap, D), xt.dtype).at[slot].add(
        jnp.where(valid[:, None], src, 0))
    buf = buf.reshape(n_ranks, e_loc * cap, D)

    # exchange: axis0 becomes source-rank after all_to_all
    recv = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=0,
                              tiled=False)
    recv = recv.reshape(n_ranks, e_loc, cap, D).transpose(1, 0, 2, 3)
    recv = recv.reshape(e_loc, n_ranks * cap, D)

    out = _expert_ffn(wg, wu, wd, recv)

    out = out.reshape(e_loc, n_ranks, cap, D).transpose(1, 0, 2, 3)
    out = out.reshape(n_ranks, e_loc * cap, D)
    back = jax.lax.all_to_all(out, model_axis, split_axis=0, concat_axis=0,
                              tiled=False)
    back = back.reshape(E * cap, D)

    # combine: weighted sum of each token's surviving expert outputs
    gathered = back[slot] * jnp.where(valid, flat_w, 0.0)[:, None].astype(
        back.dtype)
    y = jnp.zeros((T, D), back.dtype).at[flat_t].add(gathered)
    return y.reshape(B, S, D), aux["load_balance"], aux["router_z"]


def _moe_sp(ctx: Ctx, p, x):
    """x: (B, S, D) with batch->data(+pod), S->model (SP residual)."""
    cfg = ctx.cfg
    model_axis = ctx.parallel.model_axis
    n = ctx.model_axis_size
    if ctx.mesh is None or n == 1:
        y, lb, rz = _moe_dense_fallback(cfg, p, x)
        return y, {"load_balance": lb, "router_z": rz}
    baxes = ctx.batch_axes()
    bspec = baxes if baxes else None
    x_spec = P(bspec, model_axis, None)
    w_full = P(None, None)
    e_spec = P(model_axis, None, None)

    def body(x_loc, w_router, wg, wu, wd):
        return _moe_local_a2a(cfg, model_axis, n, x_loc, w_router, wg, wu, wd)

    y, lb, rz = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(x_spec, w_full, e_spec, e_spec, e_spec),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )(x, p["router"], p["wg"], p["wu"], p["wd"])
    return y, {"load_balance": lb, "router_z": rz}


# --------------------------------------------------------------------------
# Replicated-token path (decode; also single-device fallback)
# --------------------------------------------------------------------------
def _moe_dense_fallback(cfg: ModelConfig, p, x):
    """No-mesh reference: every expert computed locally via capacity buffer."""
    moe = cfg.moe
    E = moe.n_experts
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    topw, tope, probs = _topk_route(xt, p["router"], moe.top_k)
    aux = aux_losses(probs, tope, E)
    flat_e = tope.reshape(-1)
    flat_w = topw.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), moe.top_k)
    ranks = _positions_in_expert(flat_e, E)
    cap = int(np.ceil(T * moe.top_k / E * moe.capacity_factor))
    cap = max(8, int(np.ceil(cap / 8) * 8))
    valid = ranks < cap
    slot = flat_e * cap + jnp.where(valid, ranks, 0)
    buf = jnp.zeros((E * cap, D), xt.dtype).at[slot].add(
        jnp.where(valid[:, None], xt[flat_t], 0))
    out = _expert_ffn(p["wg"], p["wu"], p["wd"], buf.reshape(E, cap, D))
    out = out.reshape(E * cap, D)
    gathered = out[slot] * jnp.where(valid, flat_w, 0.0)[:, None].astype(out.dtype)
    y = jnp.zeros((T, D), out.dtype).at[flat_t].add(gathered)
    return y.reshape(B, S, D), aux["load_balance"], aux["router_z"]


def _moe_replicated(ctx: Ctx, p, x):
    """Decode path: x replicated over model; each rank computes local experts
    and psums. x: (B, S=1, D)."""
    cfg = ctx.cfg
    moe = cfg.moe
    model_axis = ctx.parallel.model_axis
    n = ctx.model_axis_size
    if ctx.mesh is None or n == 1:
        y, lb, rz = _moe_dense_fallback(cfg, p, x)
        return y, {"load_balance": lb, "router_z": rz}
    E = moe.n_experts
    e_loc = E // n
    baxes = ctx.batch_axes()
    bspec = baxes if baxes else None
    x_spec = P(bspec, None, None)
    e_spec = P(model_axis, None, None)

    cap_mult = ctx.parallel.moe_decode_cap_mult

    def body(x_loc, w_router, wg, wu, wd):
        B, S, D = x_loc.shape
        T = B * S
        xt = x_loc.reshape(T, D)
        topw, tope, probs = _topk_route(xt, w_router, moe.top_k)
        my0 = jax.lax.axis_index(model_axis) * e_loc
        local_e = tope - my0                      # (T,k) in [0, e_loc) if mine
        mine = (local_e >= 0) & (local_e < e_loc)
        flat_e = jnp.where(mine, local_e, 0).reshape(-1)
        flat_w = jnp.where(mine, topw, 0.0).reshape(-1)
        flat_m = mine.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), moe.top_k)
        ranks = _positions_in_expert(
            jnp.where(flat_m, flat_e, e_loc), e_loc + 1)
        if cap_mult == 4.0:   # baseline formula (recorded in the sweep)
            cap = int(np.ceil(T * moe.top_k / max(E, 1) * 4)) + 8
            cap = int(np.ceil(cap / 8) * 8)
        else:                 # hillclimb: tight capacity, 8-lane aligned
            cap = max(8, int(np.ceil(
                np.ceil(T * moe.top_k / max(E, 1) * cap_mult) / 8) * 8))
        valid = flat_m & (ranks < cap)
        slot = flat_e * cap + jnp.where(valid, ranks, 0)
        buf = jnp.zeros((e_loc * cap, D), xt.dtype).at[slot].add(
            jnp.where(valid[:, None], xt[flat_t], 0))
        out = _expert_ffn(wg, wu, wd, buf.reshape(e_loc, cap, D))
        out = out.reshape(e_loc * cap, D)
        gathered = out[slot] * jnp.where(valid, flat_w, 0.0)[:, None].astype(
            out.dtype)
        y = jnp.zeros((T, D), out.dtype).at[flat_t].add(gathered)
        y = jax.lax.psum(y, model_axis)
        aux = aux_losses(probs, tope, E)
        return (y.reshape(B, S, D), aux["load_balance"], aux["router_z"])

    y, lb, rz = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(x_spec, P(None, None), e_spec, e_spec, e_spec),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )(x, p["router"], p["wg"], p["wu"], p["wd"])
    return y, {"load_balance": lb, "router_z": rz}


def _moe_weight_stationary(ctx: Ctx, p, x):
    """Decode MoE without moving weights: expert d_ff sharded over `data`,
    experts over `model`; the (tiny) decode activations are all-gathered over
    the batch axes, every device computes its expert x d_ff-slice partials,
    and one small psum over (data+model) combines. Weight traffic per step:
    zero collectives (weights stay resident)."""
    cfg = ctx.cfg
    moe = cfg.moe
    model_axis = ctx.parallel.model_axis
    n = ctx.model_axis_size
    baxes = ctx.batch_axes()
    if ctx.mesh is None or n == 1 or not baxes:
        y, lb, rz = _moe_dense_fallback(cfg, p, x)
        return y, {"load_balance": lb, "router_z": rz}
    E = moe.n_experts
    e_loc = E // n
    x_spec = P(baxes, None, None)
    wg_spec = P(model_axis, None, baxes)
    wd_spec = P(model_axis, baxes, None)
    cap_mult = ctx.parallel.moe_decode_cap_mult

    def body(x_loc, w_router, wg, wu, wd):
        B_loc, S, D = x_loc.shape
        x_all = jax.lax.all_gather(x_loc, baxes, axis=0, tiled=True)
        B = x_all.shape[0]
        T = B * S
        xt = x_all.reshape(T, D)
        topw, tope, probs = _topk_route(xt, w_router, moe.top_k)
        my0 = jax.lax.axis_index(model_axis) * e_loc
        local_e = tope - my0
        mine = (local_e >= 0) & (local_e < e_loc)
        flat_e = jnp.where(mine, local_e, 0).reshape(-1)
        flat_w = jnp.where(mine, topw, 0.0).reshape(-1)
        flat_m = mine.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), moe.top_k)
        ranks = _positions_in_expert(
            jnp.where(flat_m, flat_e, e_loc), e_loc + 1)
        cap = max(8, int(np.ceil(
            np.ceil(T * moe.top_k / max(E, 1) * cap_mult) / 8) * 8))
        valid = flat_m & (ranks < cap)
        slot = flat_e * cap + jnp.where(valid, ranks, 0)
        buf = jnp.zeros((e_loc * cap, D), xt.dtype).at[slot].add(
            jnp.where(valid[:, None], xt[flat_t], 0))
        out = _expert_ffn(wg, wu, wd, buf.reshape(e_loc, cap, D))
        out = out.reshape(e_loc * cap, D)
        gathered = out[slot] * jnp.where(valid, flat_w, 0.0)[:, None].astype(
            out.dtype)
        y = jnp.zeros((T, D), out.dtype).at[flat_t].add(gathered)
        y = jax.lax.psum(y, (model_axis,) + baxes)
        aux = aux_losses(probs, tope, E)
        # return only this data-rank's batch slice
        d_idx = jax.lax.axis_index(baxes[-1])
        if len(baxes) > 1:
            d_idx = jax.lax.axis_index(baxes[0]) * ctx.mesh.shape[baxes[-1]] \
                + d_idx
        y = jax.lax.dynamic_slice_in_dim(y.reshape(B, S, D),
                                         d_idx * B_loc, B_loc, axis=0)
        return y, aux["load_balance"], aux["router_z"]

    y, lb, rz = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(x_spec, P(None, None), wg_spec, wg_spec, wd_spec),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )(x, p["router"], p["wg"], p["wu"], p["wd"])
    return y, {"load_balance": lb, "router_z": rz}


def moe_mlp_apply(ctx: Ctx, p, x) -> Tuple[jnp.ndarray, Dict]:
    h = _norm(ctx.cfg, p, x)
    if ctx.mode == "decode":
        if ctx.parallel.moe_weight_stationary:
            y, aux = _moe_weight_stationary(ctx, p, h)
        else:
            y, aux = _moe_replicated(ctx, p, h)
    else:
        h = constrain(h, ctx.rules, ("batch", "seq", None))
        y, aux = _moe_sp(ctx, p, h)
    y = constrain(y, ctx.rules, ("batch", "seq", None))
    return x + y, aux


def moe_block_apply(ctx: Ctx, p, x, cache=None):
    x, new_cache = attn_apply(ctx, p["attn"], x, cache)
    x, aux = moe_mlp_apply(ctx, p["moe"], x)
    return x, new_cache, aux
