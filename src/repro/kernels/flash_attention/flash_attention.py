"""Pallas TPU flash-attention forward kernel (causal / local-window, GQA).

Grid: (batch*q_heads, n_q_blocks, n_kv_blocks) with the kv dimension
sequential ("arbitrary") so the online-softmax state (m, l, acc) lives in
VMEM scratch across kv steps. Fully-masked blocks are skipped with pl.when,
so causal FLOPs track the triangle. GQA is handled in the k/v BlockSpec
index maps (kv head = q head // group). Layout: (B*H, S, D) per operand with
block (1, block_q, head_dim) — head_dim is the lane dimension (128-aligned
for the assigned architectures).

Validated against ``ref.naive_attention`` in interpret mode on CPU
(tests/test_kernels.py sweeps shapes, dtypes, window sizes).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, n_kv_blocks: int,
                  causal: bool, window: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    needed = jnp.asarray(True)
    if causal:
        needed &= k_start <= q_start + block_q - 1
    if window > 0:
        needed &= k_start + block_k - 1 > q_start - window

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         block_q: int = 128, block_k: int = 128,
                         group: int = 1, interpret: bool = False):
    """q: (B*Hq, Sq, D); k, v: (B*Hkv, Sk, D); group = Hq // Hkv per batch
    element. ``q`` rows are ordered (b, h); kv row for q row i is
    (i // (Hkv*group)) * Hkv + (i % (Hkv*group)) // group.
    """
    BHq, Sq, D = q.shape
    BHkv, Sk, _ = k.shape
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    nq, nk = Sq // block_q, Sk // block_k
    scale = D ** -0.5
    assert BHq == BHkv * group, (BHq, BHkv, group)

    def q_index(bh, qi, ki):
        return (bh, qi, 0)

    # q rows are (b, h)-ordered with h = 0..Hq-1 and Hq = Hkv*group, so the
    # kv row for q row bh is exactly bh // group.
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, n_kv_blocks=nk,
        causal=causal, window=window, scale=scale)

    return pl.pallas_call(
        kernel,
        grid=(BHq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_index),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki: (bh // group, ki, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki: (bh // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_index),
        out_shape=jax.ShapeDtypeStruct((BHq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
