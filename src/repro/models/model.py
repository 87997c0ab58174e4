"""ModelApi: unified build/init/loss/prefill/decode for every architecture.

``build_model(cfg, parallel, mesh)`` returns a :class:`ModelApi` whose
methods are pure functions suitable for ``jax.jit`` with shardings derived
from the logical-axis rules. All families scan over layers so HLO size is
O(1) in depth.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig
from repro.models import layers as L
from repro.models import mamba2, moe, rglru, whisper
from repro.models import transformer as T
from repro.sharding.partition import Rules, constrain, make_rules

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _remat(body: Callable, policy: str) -> Callable:
    if policy == "none":
        return body
    if policy == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(body)  # "block"/"full": save only carries


# --------------------------------------------------------------------------
# Loss
# --------------------------------------------------------------------------
def xent_loss(logits, labels, rules: Rules):
    """Masked softmax cross-entropy; labels < 0 are ignored."""
    mask = (labels >= 0)
    labels_c = jnp.maximum(labels, 0)
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels_c[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    n = jnp.maximum(mask.sum(), 1)
    return nll.sum() / n, {"tokens": mask.sum()}


def fused_xent_loss(x, table, labels, rules: Rules, tied: bool,
                    chunk: int = 1024):
    """Chunked-vocab fused softmax-xent: never materializes (B,S,V) logits.

    Scans over sequence chunks; each chunk computes its logits, reduces to
    (lse, gold) and discards them. Grad recomputes per chunk (checkpointed).
    """
    B, S, D = x.shape
    mask = labels >= 0
    labels_c = jnp.maximum(labels, 0)
    nchunk = max(1, S // chunk)
    xs = x.reshape(B, nchunk, S // nchunk, D).transpose(1, 0, 2, 3)
    ls = labels_c.reshape(B, nchunk, S // nchunk).transpose(1, 0, 2)

    @jax.checkpoint
    def step(carry, inp):
        xc, lc = inp
        xf = xc.astype(jnp.float32)
        if tied:
            logits = jnp.einsum("bsd,vd->bsv", xf, table.astype(jnp.float32))
        else:
            logits = jnp.einsum("bsd,dv->bsv", xf, table.astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return carry + (lse - gold).sum(where=None), None

    # accumulate sum of per-token nll over chunks, then mask-normalize.
    # (mask handled by zeroing nll of masked tokens inside)
    def step_masked(carry, inp):
        xc, lc, mc = inp
        xf = xc.astype(jnp.float32)
        if tied:
            logits = jnp.einsum("bsd,vd->bsv", xf, table.astype(jnp.float32))
        else:
            logits = jnp.einsum("bsd,dv->bsv", xf, table.astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return carry + ((lse - gold) * mc).sum(), None

    ms = mask.reshape(B, nchunk, S // nchunk).transpose(1, 0, 2)
    total, _ = jax.lax.scan(jax.checkpoint(step_masked), jnp.float32(0.0),
                            (xs, ls, ms))
    n = jnp.maximum(mask.sum(), 1)
    return total / n, {"tokens": mask.sum()}


# --------------------------------------------------------------------------
# ModelApi
# --------------------------------------------------------------------------
@dataclasses.dataclass
class ModelApi:
    cfg: ModelConfig
    parallel: ParallelConfig
    mesh: Any
    defs: Any
    rules_p: Rules
    rules_a: Rules
    recipe: str

    # ---- params ----------------------------------------------------------
    def init(self, rng) -> Any:
        """Random params, each leaf drawn on its sharding (FSDP/TP)."""
        shardings = None if self.mesh is None else self.param_shardings()
        return L.init_params(rng, self.defs, DTYPES[self.parallel.param_dtype],
                             shardings)

    def param_shapes(self) -> Any:
        return L.param_shapes(self.defs, DTYPES[self.parallel.param_dtype])

    def param_pspecs(self) -> Any:
        return jax.tree.map(
            lambda d: self.rules_p.spec(d.logical, d.shape),
            self.defs, is_leaf=L.is_def)

    def param_shardings(self) -> Any:
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self.param_pspecs())

    def n_params(self) -> int:
        return int(sum(np.prod(d.shape) for d in
                       jax.tree.leaves(self.defs, is_leaf=L.is_def)))

    # ---- ctx --------------------------------------------------------------
    def _ctx(self, mode: str, positions) -> T.Ctx:
        return T.Ctx(cfg=self.cfg, parallel=self.parallel, rules=self.rules_a,
                     mesh=self.mesh, mode=mode, positions=positions,
                     recipe=self.recipe, q_block=self.parallel.q_block,
                     kv_block=self.parallel.kv_block)

    def _compute_dtype(self):
        return DTYPES[self.parallel.compute_dtype] if \
            self.cfg.dtype == "bfloat16" else DTYPES[self.cfg.dtype]

    def _cast(self, params):
        cd = self._compute_dtype()
        return jax.tree.map(
            lambda a: a.astype(cd) if a.dtype == jnp.float32 and
            jnp.issubdtype(a.dtype, jnp.floating) else a, params)

    # ---- forward ----------------------------------------------------------
    def _embed_in(self, params, batch, ctx):
        cfg = self.cfg
        tokens = batch["tokens"]
        x = T.embed_tokens(cfg, params, tokens, self.rules_a,
                           self._compute_dtype())
        if cfg.family == "vlm":
            nv = cfg.n_vision_tokens
            patches = batch["patches"].astype(x.dtype)
            x = jnp.concatenate([patches, x[:, nv:, :]], axis=1)
            x = constrain(x, self.rules_a, ("batch", "seq", None))
        return x

    def _run_blocks(self, params, x, ctx, caches=None):
        """Dispatch per family; returns (x, new_caches, aux)."""
        cfg = self.cfg
        policy = self.parallel.remat if ctx.mode == "train" else "none"
        fam = cfg.family

        if fam in ("dense", "vlm"):
            return self._run_uniform(params["blocks"], x, ctx, caches,
                                     T.dense_block_apply, policy)
        if fam == "moe":
            return self._run_moe(params["blocks"], x, ctx, caches, policy)
        if fam == "ssm":
            return self._run_uniform(params["blocks"], x, ctx, caches,
                                     mamba2.ssm_block_apply, policy)
        if fam == "hybrid":
            return self._run_hybrid(params, x, ctx, caches, policy)
        raise ValueError(fam)

    def _run_uniform(self, blocks, x, ctx, caches, apply_fn, policy):
        collect = ctx.mode == "prefill"
        if ctx.mode == "decode":
            def body(carry, xs):
                blk, cache = xs
                y, c = apply_fn(ctx, blk, carry, cache)
                return y, c
            x, new_caches = jax.lax.scan(body, x, (blocks, caches))
            return x, new_caches, {}

        def body(carry, blk):
            y, c = apply_fn(ctx, blk, carry)
            return y, (c if collect else None)
        body = _remat(body, policy)
        x, ys = jax.lax.scan(body, x, blocks)
        return x, (ys if collect else None), {}

    def _run_moe(self, blocks, x, ctx, caches, policy):
        collect = ctx.mode == "prefill"
        if ctx.mode == "decode":
            def body(carry, xs):
                blk, cache = xs
                y, c, _aux = moe.moe_block_apply(ctx, blk, carry, cache)
                return y, c
            x, new_caches = jax.lax.scan(body, x, (blocks, caches))
            return x, new_caches, {}

        def body(carry, blk):
            y, lb, rz = carry
            y, c, aux = moe.moe_block_apply(ctx, blk, y)
            return ((y, lb + aux["load_balance"], rz + aux["router_z"]),
                    (c if collect else None))
        body = _remat(body, policy)
        (x, lb, rz), ys = jax.lax.scan(
            body, (x, jnp.float32(0.0), jnp.float32(0.0)), blocks)
        n = self.cfg.n_layers
        aux = {"load_balance": lb / n, "router_z": rz / n}
        return x, (ys if collect else None), aux

    def _run_hybrid(self, params, x, ctx, caches, policy):
        collect = ctx.mode == "prefill"
        kinds = {"rec": rglru.rec_block_apply, "attn": rglru.attn_block_apply_rg}
        pattern = self.cfg.rglru.pattern

        def group_body(carry, xs):
            if ctx.mode == "decode":
                blk, cache = xs
            else:
                blk = xs
                cache = {k: None for k in blk}
            y = carry
            outs = {}
            for i, kind in enumerate(pattern):
                key = f"{kind}{i}"
                y, c = kinds[kind](ctx, blk[key], y, cache.get(key))
                if collect or ctx.mode == "decode":
                    outs[key] = c
            return y, (outs if outs else None)

        def tail_body(carry, xs):
            if ctx.mode == "decode":
                blk, cache = xs
            else:
                blk, cache = xs, None
            y, c = rglru.rec_block_apply(ctx, blk, carry, cache)
            return y, (c if (collect or ctx.mode == "decode") else None)

        gb = _remat(group_body, policy) if ctx.mode == "train" else group_body
        tb = _remat(tail_body, policy) if ctx.mode == "train" else tail_body

        new_caches = {}
        if ctx.mode == "decode":
            x, gc = jax.lax.scan(gb, x, (params["groups"], caches["groups"]))
            new_caches["groups"] = gc
            if "tail" in params:
                x, tc = jax.lax.scan(tb, x, (params["tail"], caches["tail"]))
                new_caches["tail"] = tc
        else:
            x, gc = jax.lax.scan(gb, x, params["groups"])
            new_caches["groups"] = gc
            if "tail" in params:
                x, tc = jax.lax.scan(tb, x, params["tail"])
                new_caches["tail"] = tc
        if not collect and ctx.mode != "decode":
            new_caches = None
        return x, new_caches, {}

    # ---- public entry points ----------------------------------------------
    def loss_fn(self, params, batch):
        cfg = self.cfg
        params = self._cast(params)
        if cfg.family == "audio":
            return self._whisper_loss(params, batch)
        B, S = batch["tokens"].shape
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
        ctx = self._ctx("train", positions)
        x = self._embed_in(params, batch, ctx)
        x, _, aux = self._run_blocks(params, x, ctx)
        x = T.final_norm(cfg, params, x)
        if self.parallel.fused_xent:
            table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
            loss, metrics = fused_xent_loss(x, table, batch["labels"],
                                            self.rules_a, cfg.tie_embeddings)
        else:
            logits = T.lm_logits(cfg, params, x, self.rules_a)
            loss, metrics = xent_loss(logits, batch["labels"], self.rules_a)
        if aux:
            loss = loss + (cfg.moe.router_aux_coef * aux["load_balance"]
                           + 1e-4 * aux["router_z"])
            metrics.update(aux)
        metrics["loss"] = loss
        return loss, metrics

    def prefill_fn(self, params, batch):
        cfg = self.cfg
        params = self._cast(params)
        if cfg.family == "audio":
            return self._whisper_prefill(params, batch)
        B, S = batch["tokens"].shape
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
        ctx = self._ctx("prefill", positions)
        x = self._embed_in(params, batch, ctx)
        x, caches, _ = self._run_blocks(params, x, ctx)
        x = T.final_norm(cfg, params, x)
        logits = T.lm_logits(cfg, params, x[:, -1:, :], self.rules_a)
        return logits, caches

    def decode_fn(self, params, caches, tokens, pos):
        """tokens: (B,1) int32; pos: (B,) position of the new token."""
        cfg = self.cfg
        params = self._cast(params)
        if cfg.family == "audio":
            return self._whisper_decode(params, caches, tokens, pos)
        ctx = self._ctx("decode", pos)
        x = T.embed_tokens(cfg, params, tokens, self.rules_a,
                           self._compute_dtype())
        x, new_caches, _ = self._run_blocks(params, x, ctx, caches)
        x = T.final_norm(cfg, params, x)
        logits = T.lm_logits(cfg, params, x, self.rules_a)
        return logits, new_caches

    # ---- whisper ----------------------------------------------------------
    def _whisper_loss(self, params, batch):
        cfg = self.cfg
        ctx = self._ctx("train", None)
        enc = whisper.encode(ctx, params, batch["frames"].astype(
            self._compute_dtype()))
        B, S = batch["tokens"].shape
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
        ctx.positions = positions
        x = whisper.decoder_embed(ctx, params, batch["tokens"],
                                  positions, self._compute_dtype())
        x = constrain(x, self.rules_a, ("batch", "seq", None))
        x, _ = whisper.run_decoder_train(ctx, params, x, enc)
        x = L.layer_norm(x, params["final_ln"], params["final_ln_b"],
                         cfg.norm_eps)
        logits = T.lm_logits(cfg, params, x, self.rules_a)
        loss, metrics = xent_loss(logits, batch["labels"], self.rules_a)
        metrics["loss"] = loss
        return loss, metrics

    def _whisper_prefill(self, params, batch):
        cfg = self.cfg
        ctx = self._ctx("prefill", None)
        enc = whisper.encode(ctx, params, batch["frames"].astype(
            self._compute_dtype()))
        B, S = batch["tokens"].shape
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
        ctx.positions = positions
        x = whisper.decoder_embed(ctx, params, batch["tokens"], positions,
                                  self._compute_dtype())
        x, caches = whisper.run_decoder_train(ctx, params, x, enc)
        x = L.layer_norm(x, params["final_ln"], params["final_ln_b"],
                         cfg.norm_eps)
        logits = T.lm_logits(cfg, params, x[:, -1:, :], self.rules_a)
        return logits, caches

    def _whisper_decode(self, params, caches, tokens, pos):
        cfg = self.cfg
        ctx = self._ctx("decode", pos)
        x = whisper.decoder_embed(ctx, params, tokens, pos[:, None],
                                  self._compute_dtype())
        x, new_caches = whisper.run_decoder_decode(ctx, params, x, caches)
        x = L.layer_norm(x, params["final_ln"], params["final_ln_b"],
                         cfg.norm_eps)
        logits = T.lm_logits(cfg, params, x, self.rules_a)
        return logits, new_caches


# --------------------------------------------------------------------------
# Builder
# --------------------------------------------------------------------------
def build_defs(cfg: ModelConfig, parallel: Optional[ParallelConfig] = None):
    if cfg.family == "audio":
        return whisper.whisper_defs(cfg)
    if cfg.family == "moe":
        ws = bool(parallel and parallel.moe_weight_stationary)
        return T.lm_defs(cfg, lambda c: moe.moe_block_defs(c, ws))
    if cfg.family == "ssm":
        return T.lm_defs(cfg, mamba2.ssm_block_defs)
    if cfg.family == "hybrid":
        pattern = cfg.rglru.pattern
        plen = len(pattern)
        n_groups, tail = divmod(cfg.n_layers, plen)
        group_defs = {}
        for i, kind in enumerate(pattern):
            group_defs[f"{kind}{i}"] = (
                rglru.rec_block_defs(cfg) if kind == "rec"
                else rglru.attn_block_defs_rg(cfg))
        D, V = cfg.d_model, cfg.vocab_size
        defs = {
            "embed": L.ParamDef((V, D), ("vocab", "embed") if
                                cfg.tie_embeddings else ("vocab_in", "embed_in")),
            "final_ln": L.ParamDef((D,), ("embed",), "ones"),
            "groups": L.stack_defs(group_defs, n_groups),
        }
        if not cfg.tie_embeddings:
            defs["lm_head"] = L.ParamDef((D, V), ("embed", "vocab"))
        if tail:
            assert all(k == "rec" for k in
                       [pattern[i % plen] for i in range(n_groups * plen,
                                                         cfg.n_layers)]), \
                "tail layers must be recurrent"
            defs["tail"] = L.stack_defs(rglru.rec_block_defs(cfg), tail)
        return defs
    # dense / vlm
    return T.lm_defs(cfg, T.dense_block_defs)


def build_model(cfg: ModelConfig, parallel: ParallelConfig, mesh) -> ModelApi:
    rules_p, rules_a = make_rules(mesh, parallel)
    tp = mesh.shape.get(parallel.model_axis, 1) if mesh is not None else 1
    if parallel.pad_attention_heads and tp > 1 and cfg.n_heads % tp:
        # hillclimb lever: pad Hq to a TP multiple so head-parallel attention
        # applies (extra heads are real-but-redundant capacity; FLOPs grow by
        # padded/Hq on attention only, collectives shrink from ZeRO-gather to
        # Megatron-TP). Requires the padded count to stay a GQA multiple.
        padded = ((cfg.n_heads + tp - 1) // tp) * tp
        if cfg.n_kv_heads and padded % cfg.n_kv_heads == 0:
            cfg = dataclasses.replace(cfg, n_heads=padded)
    recipe = T.recipe_for(cfg, tp)
    defs = build_defs(cfg, parallel)
    return ModelApi(cfg=cfg, parallel=parallel, mesh=mesh, defs=defs,
                    rules_p=rules_p, rules_a=rules_a, recipe=recipe)
