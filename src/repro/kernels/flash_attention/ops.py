"""jit'd public wrapper for the flash-attention Pallas kernel.

Accepts the model-layer layout (B, S, H, D); transposes to the kernel's
(B*H, S, D) layout; handles GQA via the kernel's index-map grouping.
``interpret=True`` runs the kernel body in the Pallas interpreter (CPU
validation); the default compiles it for the TPU.

No model path calls this kernel: the models attend through the XLA path
(``repro.models.layers.flash_attention_jnp`` / ``masked_attention``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D) -> (B, Sq, Hq, D)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    qt = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               block_q=block_q, block_k=block_k, group=G,
                               interpret=interpret)
    return out.reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)
