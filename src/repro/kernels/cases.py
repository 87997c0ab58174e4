"""One case per Pallas kernel at the widths of the config it serves.

The TPU compile tests compile these shapes for a described v5e chip, and
``chip_smoke.py`` runs them on the chip and checks each against its ``ref``.
``inputs(key)`` draws the operands on the device; ``jax.eval_shape`` of it
gives the shapes without drawing anything.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import naive_attention
from repro.kernels.moe_gmm.ops import grouped_matmul
from repro.kernels.moe_gmm.ref import gmm_reference
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_reference
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_reference


@dataclasses.dataclass(frozen=True)
class KernelCase:
    source: str                       # config whose widths these are
    inputs: Callable                  # key -> operands
    run: Callable                     # (*operands, interpret) -> output(s)
    ref: Callable                     # (*operands) -> output(s)
    atol: float
    rtol: float


def _flash_inputs(key):
    kq, kk, kv = jax.random.split(key, 3)
    B, S, Hq, Hkv, D = 1, 2048, 24, 8, 128
    return (jax.random.normal(kq, (B, S, Hq, D), jnp.bfloat16),
            jax.random.normal(kk, (B, S, Hkv, D), jnp.bfloat16),
            jax.random.normal(kv, (B, S, Hkv, D), jnp.bfloat16))


def _ssd_inputs(key):
    kx, kd, kb, kc, ka = jax.random.split(key, 5)
    B, S, H, P, N = 1, 1024, 64, 64, 128
    return (jax.random.normal(kx, (B, S, H, P)),
            jnp.abs(jax.random.normal(kd, (B, S, H))) * 0.1,
            jax.random.normal(kb, (B, S, N)),
            jax.random.normal(kc, (B, S, N)),
            -jnp.abs(jax.random.normal(ka, (H,))) - 0.1)


def _ssd_ref(xh, dt, Bm, Cm, A):
    """ssd_reference in the model layout the ops wrapper takes."""
    B, S, H, P = xh.shape
    N = Bm.shape[-1]
    y, h = ssd_reference(
        xh.transpose(0, 2, 1, 3).reshape(B * H, S, P),
        dt.transpose(0, 2, 1).reshape(B * H, S, 1),
        jnp.broadcast_to(Bm[:, None], (B, H, S, N)).reshape(B * H, S, N),
        jnp.broadcast_to(Cm[:, None], (B, H, S, N)).reshape(B * H, S, N),
        jnp.broadcast_to(A[None, :], (B, H)).reshape(B * H, 1))
    return y.reshape(B, H, S, P).transpose(0, 2, 1, 3), h.reshape(B, H, P, N)


def _rglru_inputs(key):
    ka, kb = jax.random.split(key)
    B, S, C = 1, 1024, 4096
    return (-jnp.abs(jax.random.normal(ka, (B, S, C))) * 2.0,
            jax.random.normal(kb, (B, S, C)))


def _gmm_inputs(key):
    kx, kw, kc = jax.random.split(key, 3)
    E, C, D, F = 64, 512, 2048, 1024
    return (jax.random.normal(kx, (E, C, D), jnp.bfloat16),
            jax.random.normal(kw, (E, D, F), jnp.bfloat16) * D ** -0.5,
            jax.random.randint(kc, (E,), 0, C + 1, jnp.int32))


CASES: Dict[str, KernelCase] = {
    "flash_attention": KernelCase(
        "minitron-4b attention (24 q / 8 kv heads, head_dim 128), S=2048",
        _flash_inputs,
        lambda q, k, v, interpret: flash_attention(q, k, v,
                                                   interpret=interpret),
        naive_attention, atol=3e-2, rtol=3e-2),
    "ssd_scan": KernelCase(
        "mamba2-1.3b SSD (64 heads, P=64, N=128, chunk 256), S=1024",
        _ssd_inputs,
        lambda x, dt, b, c, a, interpret: ssd_scan(x, dt, b, c, a, chunk=256,
                                                   interpret=interpret),
        # y reaches ~45 here: f32 sums of that size carry ~1e-4 of it
        # (5.1e-3 measured on a TPU v5e); bf16-rounded operands give 0.14
        _ssd_ref, atol=2e-2, rtol=1e-3),
    "rglru_scan": KernelCase(
        "recurrentgemma-9b RG-LRU (lru_width 4096, chunk 256), S=1024",
        _rglru_inputs,
        lambda a, b, interpret: rglru_scan(a, b, chunk=256,
                                           interpret=interpret),
        rglru_reference, atol=1e-4, rtol=1e-4),
    "moe_gmm": KernelCase(
        "olmoe-1b-7b experts (64 x d_model 2048 x d_ff 1024), capacity 512",
        _gmm_inputs,
        lambda x, w, c, interpret: grouped_matmul(x, w, c,
                                                  interpret=interpret),
        gmm_reference, atol=3e-2, rtol=2e-2),
}
