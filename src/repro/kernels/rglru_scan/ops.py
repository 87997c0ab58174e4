"""jit'd wrapper for the RG-LRU scan kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.rglru_scan.rglru_scan import rglru_scan_bc


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def rglru_scan(log_a, b, *, chunk: int = 256, interpret: bool = False):
    """log_a, b: (B, S, C) -> (B, S, C) recurrence outputs."""
    return rglru_scan_bc(log_a, b, chunk=chunk, interpret=interpret)
