"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Per (batch, head): h_t = exp(A*dt_t) h_{t-1} + dt_t B_t (x) x_t;
y_t = C_t . h_t. Grid: (B*H, n_chunks) with the chunk dimension sequential —
the inter-chunk state (P, N) lives in VMEM scratch. Within a chunk the
intra-chunk quadratic form runs on the MXU:

    y_intra = (tril(exp(Lc_i - Lc_j)) * (C B^T) * dt_j) @ x
    y_inter = exp(Lc) * (C @ h_prev^T)
    h_new   = exp(Ltot) h_prev + ((exp(Ltot - Lc) * dt) B)^T @ x

This is the TPU-native blocking of the SSD algorithm (HBM->VMEM chunk
streaming; MXU for the two (Q,Q)/(Q,N) matmuls), replacing the GPU paper's
warp-level implementation. Validated against ref.ssd_reference (sequential
scan oracle) in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, hout_ref, h_scr,
                *, chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)        # (Q, 1)
    Bm = b_ref[0].astype(jnp.float32)         # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)         # (Q, N)
    A = a_ref[pl.program_id(0)]               # scalar (per head), SMEM

    # every per-token vector stays a (Q, 1) column or a (1, Q) row: Mosaic
    # has no cumsum, so the prefix sums are a lower-triangular matmul. All
    # dots run at full f32 precision (the MXU default rounds to bf16).
    exact = dict(precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)
    iq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iq >= jq
    tril = causal.astype(jnp.float32)
    logd = dt * A                                                 # (Q,1)
    Lc = jax.lax.dot(tril, logd, **exact)                         # (Q,1)
    Lc_row = jax.lax.dot_general(logd, tril, (((0,), (1,)), ((), ())),
                                 **exact)                         # (1,Q)
    Ltot = jnp.sum(logd, axis=0, keepdims=True)                   # (1,1)

    # intra-chunk: M[i, j] = exp(Lc_i - Lc_j) (C_i . B_j) for j <= i,
    # applied to dt_j x_j
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             **exact)                             # (Q,Q)
    M = jnp.where(causal, jnp.exp(Lc - Lc_row), 0.0) * CB
    y = jax.lax.dot(M, dt * x, **exact)                          # (Q,P)

    # inter-chunk: y += exp(Lc) * C @ h_prev^T   (h: (P,N))
    h_prev = h_scr[...]
    y += jnp.exp(Lc) * jax.lax.dot_general(
        Cm, h_prev, (((1,), (1,)), ((), ())), **exact)

    # state update: h_new = exp(Ltot) h_prev + x^T @ (exp(Ltot-Lc)*dt*B)
    w = jnp.exp(Ltot - Lc) * dt * Bm                              # (Q,N)
    h_scr[...] = jnp.exp(Ltot) * h_prev + jax.lax.dot_general(
        x, w, (((0,), (0,)), ((), ())), **exact)

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        hout_ref[0] = h_scr[...].astype(hout_ref.dtype)


def ssd_scan_bh(x, dt, Bm, Cm, A, *, chunk: int = 128,
                interpret: bool = False):
    """x: (BH, S, P); dt: (BH, S, 1); Bm, Cm: (BH, S, N); A: (BH, 1).

    Returns (y: (BH, S, P), h_final: (BH, P, N)). fp32 recommended.
    """
    BH, S, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    y, h = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            # one scalar per (batch, head): the whole vector sits in SMEM
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, P, N), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), x.dtype),
            jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, Bm, Cm, A.reshape(BH).astype(jnp.float32))
    return y, h
