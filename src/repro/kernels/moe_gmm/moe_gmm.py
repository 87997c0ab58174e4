"""Pallas TPU grouped (per-expert) matmul kernel for MoE.

Computes out[e] = x[e] @ w[e] for E experts with per-expert valid row counts
(capacity buffers are padded): blocks whose row range is entirely beyond the
expert's count are skipped with pl.when, so padded capacity costs no MXU
work — the Pallas analogue of a ragged GEMM (dropless MoE on TPU).

Grid: (E, C/block_c, F/block_f, D/block_d); the contraction dim is the
innermost sequential axis accumulating into a VMEM scratch tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(cnt_ref, x_ref, w_ref, o_ref, acc_scr, *, block_c: int,
                block_d: int, n_d: int):
    e = pl.program_id(0)
    ci = pl.program_id(1)
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    count = cnt_ref[e]
    row_start = ci * block_c

    @pl.when(row_start < count)
    def _compute():
        x = x_ref[0]                       # (block_c, block_d)
        w = w_ref[0]                       # (block_d, block_f)
        acc_scr[...] += jax.lax.dot(x, w,
                                    preferred_element_type=jnp.float32)

    @pl.when(di == n_d - 1)
    def _emit():
        rows = row_start + jax.lax.broadcasted_iota(
            jnp.int32, acc_scr.shape, 0)
        valid = rows < count
        o_ref[0] = jnp.where(valid, acc_scr[...], 0.0).astype(o_ref.dtype)


def moe_gmm(x, w, counts, *, block_c: int = 128, block_f: int = 128,
            block_d: int = 128, interpret: bool = False):
    """x: (E, C, D); w: (E, D, F); counts: (E,) int32 -> out (E, C, F).

    Rows >= counts[e] are treated as padding (zeroed in the output and
    skipped by whole blocks where possible).
    """
    E, C, D = x.shape
    F = w.shape[-1]
    block_c = min(block_c, C)
    block_f = min(block_f, F)
    block_d = min(block_d, D)
    assert C % block_c == 0 and F % block_f == 0 and D % block_d == 0
    nc, nf, nd = C // block_c, F // block_f, D // block_d

    kernel = functools.partial(_gmm_kernel, block_c=block_c, block_d=block_d,
                               n_d=nd)
    return pl.pallas_call(
        kernel,
        grid=(E, nc, nf, nd),
        in_specs=[
            # per-expert row counts: the whole vector sits in SMEM
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_c, block_d),
                         lambda e, ci, fi, di: (e, ci, di)),
            pl.BlockSpec((1, block_d, block_f),
                         lambda e, ci, fi, di: (e, di, fi)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e, ci, fi, di: (e, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(counts, x, w)
