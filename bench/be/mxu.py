"""MXU-bound best-effort co-runner: a chain of four bfloat16
4096 x 4096 @ 4096 x 4096 matrix multiplications per quantum (0.55 TFLOP).
Its bytes from shapes (each product reads two 32 MiB operands and writes
one) are 0.4 GB, so a byte budget sized for the ``hbm`` co-runner seldom
holds it back. The weights are drawn on the device from the seed in one
program."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.generate import jax_key

CHAIN, WIDTH = 4, 4096
SMALL_WIDTH = 128                              # CPU test size


def make(seed: int, small: bool = False):
    """``(fn(lane), bytes one quantum moves)``; ``fn`` waits for its
    result, as the executor's quanta do."""
    width = SMALL_WIDTH if small else WIDTH

    @jax.jit
    def be_mxu_weights(key):
        kx, kw = jax.random.split(key)
        x = jax.random.normal(kx, (width, width), jnp.bfloat16)
        w = jax.random.normal(kw, (CHAIN, width, width),
                              jnp.bfloat16) * (width ** -0.5)
        return x, w

    x0, w = be_mxu_weights(jax_key(seed, 22))

    @jax.jit
    def be_mxu(x, w):
        def link(h, wi):
            return jnp.tanh(h @ wi), None
        return jax.lax.scan(link, x, w)[0].astype(jnp.float32).sum()

    def fn(lane):
        with jax.profiler.TraceAnnotation("be.mxu"):
            return float(be_mxu(x0, w))

    # the regulator charges what a full-size quantum moves, at any size
    return fn, float(CHAIN * 3 * WIDTH * WIDTH * 2)

