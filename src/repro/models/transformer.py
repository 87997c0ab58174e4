"""Dense decoder-only transformer (qwen2-*, minitron, granite, internvl2 LM).

Sharding recipes (decided per-arch at build time, see ``recipe_for``):

* ``tp``  — Megatron-style tensor parallel with sequence-parallel residual:
  the scan carry (residual stream) is sharded ("batch", "seq"->model); inside
  a block the hidden is gathered over model (GSPMD all-gather), attention
  runs with q/k/v heads sharded over model (KV expanded to Hq heads first so
  every shard is fully local), and the output projections are reduce-scattered
  back to the seq-sharded residual. Requires n_heads % tp == 0.
* ``cp``  — context parallel for archs whose head counts don't divide the
  model axis (minitron 24H, qwen2-7b 28H, internvl2 14H, whisper 8H): the
  residual stays seq-sharded, attention runs under shard_map with KV
  all-gathered over the model axis, and weights are ZeRO-3-gathered by GSPMD.

Both recipes keep parameters sharded identically (embed dim -> data/FSDP,
heads/mlp/vocab dims -> model), so checkpoints are recipe-agnostic.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.models import layers as L
from repro.sharding.partition import Rules, constrain


# --------------------------------------------------------------------------
# Context threaded through block application
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Ctx:
    cfg: ModelConfig
    parallel: ParallelConfig
    rules: Rules                      # activation rules
    mesh: Any                         # jax Mesh or None
    mode: str                         # train | prefill | decode
    positions: Any = None             # (B, S) int32 or (B,) for decode
    recipe: str = "tp"                # tp | cp
    q_block: int = 512
    kv_block: int = 1024

    @property
    def model_axis_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape.get(self.parallel.model_axis, 1)

    def batch_axes(self) -> Tuple[str, ...]:
        axes = []
        if self.mesh is not None:
            if self.parallel.pod_axis and self.parallel.pod_axis in self.mesh.shape:
                axes.append(self.parallel.pod_axis)
            if self.parallel.data_axis in self.mesh.shape:
                axes.append(self.parallel.data_axis)
        return tuple(axes)


def recipe_for(cfg: ModelConfig, tp_size: int) -> str:
    if cfg.n_heads and cfg.n_heads % max(tp_size, 1) == 0:
        return "tp"
    return "cp"


def _sp_in_project(ctx: "Ctx", x, ws):
    """Fused Megatron-SP input projection: all-gather the seq-sharded
    residual and apply K output-dim-sharded weights in ONE shard_map, so the
    backward x-grad is a single psum_scatter instead of GSPMD's grouped
    all-reduce of full activations. x: (B, S/n, D); ws: list of (D, K_i)
    sharded on K_i. Returns [(B, S, K_i/n) heads-sharded]."""
    model_axis = ctx.parallel.model_axis
    n = ctx.model_axis_size
    if ctx.mesh is None or n == 1 or x.shape[1] % n != 0:
        return [x @ w for w in ws]
    baxes = ctx.batch_axes()
    bspec = baxes if baxes else None

    def local(xl, *wl):
        h = jax.lax.all_gather(xl, model_axis, axis=1, tiled=True)
        return tuple(h @ w for w in wl)

    outs = jax.shard_map(
        local, mesh=ctx.mesh,
        in_specs=(P(bspec, model_axis, None),)
        + tuple(P(None, model_axis) for _ in ws),
        out_specs=tuple(P(bspec, None, model_axis) for _ in ws),
        check_vma=False)(x, *ws)
    return list(outs)


def _rs_project(ctx: "Ctx", h, w):
    """Megatron-SP output projection: local partial matmul + psum_scatter
    over the sequence dim (half the bytes of GSPMD's all-reduce and lands
    directly in the seq-sharded residual layout). h: (B, S, K) with K
    sharded over model; w: (K, D) sharded on K. Returns (B, S, D) with S
    sharded over model."""
    model_axis = ctx.parallel.model_axis
    n = ctx.model_axis_size
    if ctx.mesh is None or n == 1 or h.shape[1] % n != 0:
        return h @ w
    baxes = ctx.batch_axes()
    bspec = baxes if baxes else None

    def local(h_loc, w_loc):
        part = h_loc @ w_loc
        return jax.lax.psum_scatter(part, model_axis, scatter_dimension=1,
                                    tiled=True)

    return jax.shard_map(local, mesh=ctx.mesh,
                         in_specs=(P(bspec, None, model_axis),
                                   P(model_axis, None)),
                         out_specs=P(bspec, model_axis, None),
                         check_vma=False)(h, w)


# --------------------------------------------------------------------------
# Dense attention block
# --------------------------------------------------------------------------
def attn_defs(cfg: ModelConfig, cross: bool = False) -> Dict[str, L.ParamDef]:
    D, Q, KV = cfg.d_model, cfg.q_dim, cfg.kv_dim
    d = {
        "ln": L.ParamDef((D,), ("embed",), "ones"),
        "wq": L.ParamDef((D, Q), ("embed", "heads")),
        "wk": L.ParamDef((D, KV), ("embed", "kv")),
        "wv": L.ParamDef((D, KV), ("embed", "kv")),
        "wo": L.ParamDef((Q, D), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        d["bq"] = L.ParamDef((Q,), ("heads",), "zeros")
        d["bk"] = L.ParamDef((KV,), ("kv",), "zeros")
        d["bv"] = L.ParamDef((KV,), ("kv",), "zeros")
    if cfg.norm_style() == "layernorm":
        d["ln_b"] = L.ParamDef((D,), ("embed",), "zeros")
    return d


def mlp_defs(cfg: ModelConfig) -> Dict[str, L.ParamDef]:
    D, F = cfg.d_model, cfg.d_ff
    d = {"ln": L.ParamDef((D,), ("embed",), "ones")}
    if cfg.act == "swiglu":
        d["wg"] = L.ParamDef((D, F), ("embed", "mlp"))
        d["wu"] = L.ParamDef((D, F), ("embed", "mlp"))
        d["wd"] = L.ParamDef((F, D), ("mlp", "embed"))
    else:
        d["wi"] = L.ParamDef((D, F), ("embed", "mlp"))
        d["wo_mlp"] = L.ParamDef((F, D), ("mlp", "embed"))
        d["bi"] = L.ParamDef((F,), ("mlp",), "zeros")
        d["bo"] = L.ParamDef((D,), ("embed",), "zeros")
    if cfg.norm_style() == "layernorm":
        d["ln_b"] = L.ParamDef((D,), ("embed",), "zeros")
    return d


def dense_block_defs(cfg: ModelConfig) -> Dict[str, Any]:
    return {"attn": attn_defs(cfg), "mlp": mlp_defs(cfg)}


def _norm(cfg, p, x, prefix=""):
    if cfg.norm_style() == "layernorm":
        return L.layer_norm(x, p["ln"], p["ln_b"], cfg.norm_eps)
    return L.rms_norm(x, p["ln"], cfg.norm_eps)


def _qkv(cfg: ModelConfig, p, h):
    q = h @ p["wq"]
    k = h @ p["wk"]
    v = h @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    B, S = h.shape[0], h.shape[1]
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def _cp_attention(ctx: Ctx, q, k, v, *, causal=True, window=0):
    """Context-parallel attention: q/k/v seq-sharded over the model axis;
    KV all-gathered inside shard_map; causal mask offset by the shard index."""
    model_axis = ctx.parallel.model_axis
    n = ctx.model_axis_size
    if ctx.mesh is None or n == 1 or q.shape[1] % n != 0:
        return L.attention(q, k, v, causal=causal, window=window,
                           softcap=ctx.cfg.logit_softcap,
                           q_block=ctx.q_block, kv_block=ctx.kv_block)
    baxes = ctx.batch_axes()
    spec = P(baxes if baxes else None, model_axis, None, None)

    def local(qx, kx, vx):
        kf = jax.lax.all_gather(kx, model_axis, axis=1, tiled=True)
        vf = jax.lax.all_gather(vx, model_axis, axis=1, tiled=True)
        s_loc = qx.shape[1]
        offset = jax.lax.axis_index(model_axis) * s_loc
        return L.flash_attention_cp(
            qx, kf, vf, q_offset=offset, causal=causal, window=window,
            softcap=ctx.cfg.logit_softcap,
            q_block=min(ctx.q_block, s_loc), kv_block=ctx.kv_block)

    return jax.shard_map(local, mesh=ctx.mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def attn_apply(ctx: Ctx, p, x, cache: Optional[dict] = None,
               kv_override: Optional[Tuple] = None):
    """Self-attention sub-block. Returns (x + attn_out, new_cache_or_None).

    kv_override: (k, v, kv_positions) for cross-attention (whisper decoder).
    """
    cfg = ctx.cfg
    # gather seq -> replicated hidden for projections (tp recipe); in cp mode
    # the residual stays seq-sharded and projections run on local rows.
    h = _norm(cfg, p, x)
    use_sp_fused = (ctx.parallel.explicit_rs and ctx.recipe == "tp"
                    and ctx.mode != "decode")
    if ctx.recipe == "tp" and not use_sp_fused:
        h = constrain(h, ctx.rules, ("batch", None, None))

    if ctx.mode == "decode":
        q = (h @ p["wq"])
        if cfg.qkv_bias:
            q = q + p["bq"]
        B = h.shape[0]
        q = q.reshape(B, 1, cfg.n_heads, cfg.head_dim)
        if kv_override is None:
            knew = (h @ p["wk"])
            vnew = (h @ p["wv"])
            if cfg.qkv_bias:
                knew, vnew = knew + p["bk"], vnew + p["bv"]
            knew = knew.reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
            vnew = vnew.reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
            pos = ctx.positions  # (B,)
            if cfg.rope_theta > 0:
                q = L.rope(q, pos[:, None], cfg.rope_theta)
                knew = L.rope(knew, pos[:, None], cfg.rope_theta)
            kc = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
                c, u, (i, 0, 0)))(cache["k"], knew, pos)
            vc = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
                c, u, (i, 0, 0)))(cache["v"], vnew, pos)
            kc = constrain(kc, ctx.rules, ("batch", "kv_seq", None, None))
            vc = constrain(vc, ctx.rules, ("batch", "kv_seq", None, None))
            out = L.decode_attention(q, kc, vc, pos, window=cfg.window,
                                     softcap=cfg.logit_softcap)
            new_cache = {"k": kc, "v": vc}
        else:
            kf, vf, kv_len = kv_override
            out = L.decode_attention(
                q, kf, vf, jnp.maximum(kv_len - 1, 0), window=0,
                softcap=cfg.logit_softcap)
            new_cache = None
        attn_out = out.reshape(B, 1, cfg.q_dim) @ p["wo"]
        return x + attn_out, new_cache

    # train / prefill
    if use_sp_fused:
        qf, kf, vf = _sp_in_project(ctx, h, [p["wq"], p["wk"], p["wv"]])
        if cfg.qkv_bias:
            qf = qf + p["bq"]
            kf = kf + p["bk"]
            vf = vf + p["bv"]
        # kv heads usually don't divide the model axis: gather kv acts
        # (small) back to replicated; q stays head-sharded.
        kf = constrain(kf, ctx.rules, ("batch", None, None))
        vf = constrain(vf, ctx.rules, ("batch", None, None))
        B, Sg = qf.shape[0], qf.shape[1]
        q = qf.reshape(B, Sg, cfg.n_heads, cfg.head_dim)
        k = kf.reshape(B, Sg, cfg.n_kv_heads, cfg.head_dim)
        v = vf.reshape(B, Sg, cfg.n_kv_heads, cfg.head_dim)
    else:
        q, k, v = _qkv(cfg, p, h)
    if cfg.rope_theta > 0:
        q = L.rope(q, ctx.positions, cfg.rope_theta)
        k = L.rope(k, ctx.positions, cfg.rope_theta)
    new_cache = None
    if ctx.mode == "prefill":
        kc = constrain(k, ctx.rules, ("batch", "kv_seq", None, None))
        vc = constrain(v, ctx.rules, ("batch", "kv_seq", None, None))
        new_cache = {"k": kc, "v": vc}

    causal = True
    if ctx.recipe == "tp":
        # expand KV to Hq heads so each model shard is fully local
        G = cfg.n_heads // cfg.n_kv_heads
        if G > 1:
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
        q = constrain(q, ctx.rules, ("batch", None, "heads", None))
        k = constrain(k, ctx.rules, ("batch", None, "heads", None))
        v = constrain(v, ctx.rules, ("batch", None, "heads", None))
        out = L.attention(q, k, v, causal=causal, window=cfg.window,
                          softcap=cfg.logit_softcap,
                          q_block=ctx.q_block, kv_block=ctx.kv_block)
    else:
        out = _cp_attention(ctx, q, k, v, causal=causal, window=cfg.window)

    B, S = x.shape[0], x.shape[1]
    flat = out.reshape(B, S, cfg.q_dim)
    if ctx.parallel.explicit_rs and ctx.recipe == "tp":
        attn_out = _rs_project(ctx, flat, p["wo"])
    else:
        attn_out = flat @ p["wo"]
    attn_out = constrain(attn_out, ctx.rules, ("batch", "seq", None))
    return x + attn_out, new_cache


def mlp_apply(ctx: Ctx, p, x):
    cfg = ctx.cfg
    h = _norm(cfg, p, x)
    use_rs = (ctx.parallel.explicit_rs and ctx.recipe == "tp"
              and ctx.mode != "decode")
    if ctx.recipe == "tp" and ctx.mode != "decode" and not use_rs:
        h = constrain(h, ctx.rules, ("batch", None, None))
    if cfg.act == "swiglu":
        if use_rs:
            g, u = _sp_in_project(ctx, h, [p["wg"], p["wu"]])
        else:
            g = h @ p["wg"]
            u = h @ p["wu"]
        g = constrain(g, ctx.rules, ("batch", None, "mlp"))
        hidden = L.swiglu(g, u)
        out = _rs_project(ctx, hidden, p["wd"]) if use_rs else hidden @ p["wd"]
    else:
        if use_rs:
            (hi,) = _sp_in_project(ctx, h, [p["wi"]])
        else:
            hi = h @ p["wi"]
        hh = L.gelu(hi + p["bi"])
        out = (_rs_project(ctx, hh, p["wo_mlp"]) if use_rs
               else hh @ p["wo_mlp"]) + p["bo"]
    out = constrain(out, ctx.rules, ("batch", "seq", None))
    return x + out


def dense_block_apply(ctx: Ctx, p, x, cache=None):
    x, new_cache = attn_apply(ctx, p["attn"], x, cache)
    x = mlp_apply(ctx, p["mlp"], x)
    return x, new_cache


# --------------------------------------------------------------------------
# Full LM assembly (shared by dense / moe / ssm / hybrid via block registry)
# --------------------------------------------------------------------------
def lm_defs(cfg: ModelConfig, block_defs_fn) -> Dict[str, Any]:
    D, V = cfg.d_model, cfg.vocab_size
    defs: Dict[str, Any] = {
        # untied: input table (vocab->fsdp, embed->model); head (embed->fsdp,
        # vocab->model). tied: single table (vocab->model, embed->fsdp).
        "final_ln": L.ParamDef((D,), ("embed",), "ones"),
    }
    if cfg.tie_embeddings:
        defs["embed"] = L.ParamDef((V, D), ("vocab", "embed"), scale=1.0)
    else:
        defs["embed"] = L.ParamDef((V, D), ("vocab_in", "embed_in"), scale=1.0)
        defs["lm_head"] = L.ParamDef((D, V), ("embed", "vocab"))
    if cfg.norm_style() == "layernorm":
        defs["final_ln_b"] = L.ParamDef((D,), ("embed",), "zeros")
    defs["blocks"] = L.stack_defs(block_defs_fn(cfg), cfg.n_layers)
    return defs


def embed_tokens(cfg: ModelConfig, params, tokens, rules: Rules,
                 compute_dtype=jnp.bfloat16):
    x = jnp.take(params["embed"], tokens, axis=0)
    x = x.astype(compute_dtype)
    return constrain(x, rules, ("batch", "seq", None))


def lm_logits(cfg: ModelConfig, params, x, rules: Rules):
    xf = x.astype(jnp.float32)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", xf,
                            params["embed"].astype(jnp.float32))
    else:
        logits = jnp.einsum("bsd,dv->bsv", xf,
                            params["lm_head"].astype(jnp.float32))
    return constrain(logits, rules, ("batch", None, "vocab"))


def final_norm(cfg, params, x):
    if cfg.norm_style() == "layernorm":
        return L.layer_norm(x, params["final_ln"], params["final_ln_b"],
                            cfg.norm_eps)
    return L.rms_norm(x, params["final_ln"], cfg.norm_eps)
