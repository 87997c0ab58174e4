"""Core layers: param-def system, norms, RoPE, attention, MLP.

Conventions
-----------
* Params are nested dicts of arrays. Each model builder first constructs a
  matching nested dict of :class:`ParamDef` (shape + logical axis names +
  initializer), from which ``init`` (real arrays), ``eval_shape`` structs and
  ``PartitionSpec`` trees are all derived. Logical axis names are resolved by
  ``repro.sharding.partition.Rules``.
* Attention comes in two XLA-path flavours:
  - ``flash_attention_jnp``: double-blocked online-softmax attention
    (lax.scan over q-blocks and kv-chunks) — O(block) memory at any sequence
    length. The Pallas kernel in ``repro.kernels.flash_attention`` uses the
    same blocking, but no model path calls it: this XLA path is what runs on
    the TPU too.
  - ``decode_attention``: single-query attention against a KV cache.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------------
# Param definition system
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"        # normal | zeros | ones | small_normal
    scale: float = 1.0          # stddev multiplier for normal inits

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _init_one(rng, d: ParamDef, dtype):
    if d.init == "zeros":
        return jnp.zeros(d.shape, dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, dtype)
    fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
    std = d.scale / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(rng, d.shape, jnp.float32) * std).astype(dtype)


@partial(jax.jit, static_argnums=(1, 2, 3))
def init_leaf(rng, d: ParamDef, dtype, sharding=None):
    """Draw one leaf straight into ``dtype`` (and onto ``sharding``) in one
    program, so the float32 draw is fused into the cast and never sits in
    device memory beside the params already placed."""
    x = _init_one(rng, d, dtype)
    if sharding is not None:
        x = jax.lax.with_sharding_constraint(x, sharding)
    return x


def init_params(rng, defs, dtype=jnp.float32, shardings=None):
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    rngs = jax.random.split(rng, len(leaves))
    shards = [None] * len(leaves) if shardings is None else \
        treedef.flatten_up_to(shardings)
    vals = [init_leaf(r, d, dtype, s)
            for r, d, s in zip(rngs, leaves, shards)]
    return jax.tree.unflatten(treedef, vals)


def param_shapes(defs, dtype=jnp.float32):
    return jax.tree.map(lambda d: jax.ShapeDtypeStruct(d.shape, dtype), defs,
                        is_leaf=is_def)


def param_logical(defs):
    return jax.tree.map(lambda d: d.logical, defs, is_leaf=is_def)


def stack_defs(defs, n: int, axis_name: str = "layers"):
    """Prepend a scan dimension of size n to every ParamDef in the tree."""
    return jax.tree.map(
        lambda d: ParamDef((n,) + d.shape, (axis_name,) + d.logical, d.init,
                           d.scale),
        defs, is_leaf=is_def)


def count_params(tree) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree.leaves(tree)))


# --------------------------------------------------------------------------
# Norms / activations / embeddings
# --------------------------------------------------------------------------
def rms_norm(x, weight, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return out.astype(dtype) * weight.astype(dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out.astype(dtype) * weight.astype(dtype)) + bias.astype(dtype)


def swiglu(gate, up):
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


def gelu(x):
    return jax.nn.gelu(x.astype(jnp.float32), approximate=True).astype(x.dtype)


def sinusoidal_positions(n: int, d: int, dtype=jnp.float32):
    pos = np.arange(n)[:, None]
    dim = np.arange(d // 2)[None, :]
    inv = 1.0 / (10000 ** (2 * dim / d))
    ang = pos * inv
    table = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(table, dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------
def rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., :, None].astype(jnp.float32) * freq  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention (XLA path)
# --------------------------------------------------------------------------
NEG_INF = -1e30


def _gqa_scores(q, k):
    """q: (B, Sq, Hkv, G, D), k: (B, Sk, Hkv, D) -> (B, Hkv, G, Sq, Sk)."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                      preferred_element_type=jnp.float32)


def masked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                     q_offset=0, kv_len=None, softcap: float = 0.0):
    """Plain (materialized-scores) attention. Use only for small Sq*Sk.

    q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D). q_offset: absolute position of
    q[0] (int or (B,) array). kv_len: optional (B,) valid kv length.
    Returns (B, Sq, Hq, D).
    """
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D) * (D ** -0.5)
    s = _gqa_scores(qg, k)  # (B, Hkv, G, Sq, Sk) fp32
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    Sk = k.shape[1]
    qpos = jnp.arange(Sq)[:, None] + q_offset  # q_offset: scalar
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    if kv_len is not None:
        mask = mask[None] & (kpos[None] < kv_len[:, None, None])
        mask = mask[:, None, None]  # (B,1,1,Sq,Sk)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    return out.reshape(B, Sq, Hq, D)


def flash_attention_jnp(q, k, v, *, causal: bool = True, window: int = 0,
                        q_offset=0, q_block: int = 512, kv_block: int = 1024,
                        softcap: float = 0.0):
    """Blocked online-softmax attention; memory O(q_block * kv_block).

    q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D) with Sk >= Sq. ``q_offset`` is
    the absolute position of q[0] among the keys (may be a traced scalar —
    context parallelism passes ``axis_index * local_len``). Fully-masked kv
    blocks are skipped with lax.cond so compiled FLOPs track the causal
    triangle, not the square. Returns (B, Sq, Hq, D).
    """
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    assert Sq % q_block == 0 and Sk % kv_block == 0, (Sq, Sk, q_block, kv_block)
    nq, nk = Sq // q_block, Sk // kv_block
    scale = D ** -0.5
    q_offset = jnp.asarray(q_offset, jnp.int32)

    qb = q.reshape(B, nq, q_block, Hkv, G, D).transpose(1, 0, 2, 3, 4, 5)
    kb = k.reshape(B, nk, kv_block, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, kv_block, Hkv, D).transpose(1, 0, 2, 3, 4)

    def q_step(_, qi_and_block):
        qi, qblk = qi_and_block           # qblk: (B, q_block, Hkv, G, D)
        qblk = qblk * scale
        q_start = q_offset + qi * q_block

        def kv_step(carry, ki_and_kv):
            m, l, acc = carry
            ki, kblk, vblk = ki_and_kv
            k_start = ki * kv_block

            def compute(args):
                m, l, acc = args
                s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, kblk,
                               preferred_element_type=jnp.float32)
                if softcap > 0:
                    s = jnp.tanh(s / softcap) * softcap
                qpos = q_start + jnp.arange(q_block)[:, None]
                kpos = k_start + jnp.arange(kv_block)[None, :]
                mask = jnp.ones((q_block, kv_block), bool)
                if causal:
                    mask &= kpos <= qpos
                if window > 0:
                    mask &= kpos > qpos - window
                s = jnp.where(mask, s, NEG_INF)
                m_new = jnp.maximum(m, s.max(axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + p.sum(axis=-1)
                pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vblk.dtype), vblk)
                acc_new = acc * corr[..., None].astype(acc.dtype) + pv
                return m_new, l_new, acc_new

            # skip blocks that are entirely masked out
            needed = jnp.asarray(True)
            if causal:
                needed &= k_start <= q_start + q_block - 1
            if window > 0:
                needed &= k_start + kv_block - 1 > q_start - window
            m, l, acc = jax.lax.cond(needed, compute, lambda a: a, (m, l, acc))
            return (m, l, acc), None

        m0 = jnp.full((B, Hkv, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_block, D), v.dtype)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kb, vb))
        out = acc / jnp.maximum(l, 1e-20)[..., None].astype(acc.dtype)
        # (B, Hkv, G, q_block, D) -> (B, q_block, Hkv, G, D)
        return None, out.transpose(0, 3, 1, 2, 4)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qb))
    # outs: (nq, B, q_block, Hkv, G, D)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, Hq, D)
    return out


# context-parallel entry point: same math, explicit q_offset
flash_attention_cp = flash_attention_jnp


def attention(q, k, v, *, causal=True, window=0, softcap=0.0,
              q_block=512, kv_block=1024):
    """Dispatch: small sequences -> materialized; long -> blocked flash."""
    S = q.shape[1]
    if S <= max(q_block, 512) or S % q_block or S % kv_block:
        return masked_attention(q, k, v, causal=causal, window=window,
                                softcap=softcap)
    return flash_attention_jnp(q, k, v, causal=causal, window=window,
                               softcap=softcap, q_block=q_block,
                               kv_block=kv_block)


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     softcap: float = 0.0):
    """Single-position attention against a cache.

    q: (B, 1, Hq, D); caches: (B, Smax, Hkv, D); pos: (B,) current index
    (the new token's position; cache entries > pos are invalid).
    """
    B, _, Hq, D = q.shape
    Hkv = k_cache.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D) * (D ** -0.5)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    kpos = jnp.arange(k_cache.shape[1])[None, :]
    mask = kpos <= pos[:, None]
    if window > 0:
        mask &= kpos > pos[:, None] - window
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache)
    return out.reshape(B, 1, Hq, D)
