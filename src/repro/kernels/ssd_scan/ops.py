"""jit'd wrapper: model layout (B, S, H, P) + shared B/C -> kernel layout."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.ssd_scan import ssd_scan_bh


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(xh, dt, Bm, Cm, A, *, chunk: int = 128,
             interpret: bool = False):
    """xh: (B, S, H, P); dt: (B, S, H); Bm, Cm: (B, S, N) (shared across
    heads); A: (H,). Returns (y: (B, S, H, P), h: (B, H, P, N))."""
    B, S, H, P = xh.shape
    N = Bm.shape[-1]
    x2 = xh.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dt2 = dt.transpose(0, 2, 1).reshape(B * H, S, 1)
    Bm2 = jnp.broadcast_to(Bm[:, None], (B, H, S, N)).reshape(B * H, S, N)
    Cm2 = jnp.broadcast_to(Cm[:, None], (B, H, S, N)).reshape(B * H, S, N)
    A2 = jnp.broadcast_to(A[None, :], (B, H)).reshape(B * H, 1)
    y, h = ssd_scan_bh(x2, dt2, Bm2, Cm2, A2, chunk=chunk,
                       interpret=interpret)
    return (y.reshape(B, H, S, P).transpose(0, 2, 1, 3),
            h.reshape(B, H, P, N))
