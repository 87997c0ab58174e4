"""jit'd wrapper for the grouped-matmul kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.moe_gmm.moe_gmm import moe_gmm as _moe_gmm


@partial(jax.jit, static_argnames=("block_c", "block_f", "block_d",
                                   "interpret"))
def grouped_matmul(x, w, counts, *, block_c: int = 128, block_f: int = 128,
                   block_d: int = 128, interpret: bool = False):
    return _moe_gmm(x, w, counts, block_c=block_c, block_f=block_f,
                    block_d=block_d, interpret=interpret)
