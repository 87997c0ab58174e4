"""Pallas TPU kernel for the RG-LRU linear recurrence (RecurrentGemma).

h_t = a_t * h_{t-1} + b_t   per channel, with a_t in (0,1) given in log space.

Blocking: grid (batch, channel-block, chunk) with the chunk dimension
sequential; each (chunk, channel-block) tile of log_a and b is streamed into
VMEM once, and the carry h (1, channel-block) persists in VMEM scratch across
chunks. Inside a tile the recurrence runs exactly, one row at a time, reading
and writing the refs in 8-row (one f32 sublane tile) groups at aligned
offsets. A masked exp(cum_i - cum_j) matrix form would move the work to the
MXU but overflows for long chunks under strong decay, so the exact loop stays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8          # rows per aligned ref access (f32 sublane tile)


def _rglru_kernel(loga_ref, b_ref, y_ref, h_scr, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def group(g, h):
        r = pl.multiple_of(g * ROWS, ROWS)
        a = jnp.exp(loga_ref[0, pl.ds(r, ROWS), :].astype(jnp.float32))
        b = b_ref[0, pl.ds(r, ROWS), :].astype(jnp.float32)
        rows = []
        for t in range(ROWS):
            h = a[t:t + 1] * h + b[t:t + 1]
            rows.append(h)
        y_ref[0, pl.ds(r, ROWS), :] = jnp.concatenate(rows).astype(
            y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk // ROWS, group, h_scr[...])


def rglru_scan_bc(log_a, b, *, chunk: int = 256, block_c: int = 512,
                  interpret: bool = False):
    """log_a, b: (B, S, C) -> h_all: (B, S, C). Carry chunk-sequential."""
    B, S, C = log_a.shape
    chunk = min(chunk, S)
    block_c = min(block_c, C)
    assert S % chunk == 0 and chunk % ROWS == 0, (S, chunk)
    assert C % block_c == 0, (C, block_c)
    nc = S // chunk
    spec = pl.BlockSpec((1, chunk, block_c), lambda b_, cb, ci: (b_, ci, cb))
    return pl.pallas_call(
        functools.partial(_rglru_kernel, chunk=chunk),
        grid=(B, C // block_c, nc),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, S, C), log_a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(log_a, b)
