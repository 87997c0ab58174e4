"""HBM-bound best-effort co-runner: a 16 x 4096 x 4096 bfloat16 weight
stack (512 MiB) swept four times per quantum, 64 rows at a time, so each
layer reads 32 MiB for 2 GFLOP. Copied from the serving launcher's
background job (``launch/serve.background_job``); the weights are drawn on
the device from the seed in one program."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.generate import jax_key

LAYERS, WIDTH, ROWS, PASSES = 16, 4096, 64, 4
SMALL = dict(layers=2, width=128, rows=8)     # CPU test size


def make(seed: int, small: bool = False):
    """``(fn(lane), bytes one quantum moves)``; ``fn`` waits for its
    result, as the executor's quanta do."""
    layers, width, rows = ((SMALL["layers"], SMALL["width"], SMALL["rows"])
                           if small else (LAYERS, WIDTH, ROWS))

    @jax.jit
    def be_hbm_weights(key):
        return jax.random.normal(key, (layers, width, width),
                                 jnp.bfloat16) * 0.02

    w = be_hbm_weights(jax_key(seed, 21))
    x0 = jnp.ones((rows, width), jnp.bfloat16)

    @jax.jit
    def be_hbm(x, w):
        def layer(h, wi):
            return jnp.tanh(h @ wi), None

        def sweep(_, h):
            return jax.lax.scan(layer, h, w)[0]
        return jax.lax.fori_loop(0, PASSES, sweep,
                                 x).astype(jnp.float32).sum()

    def fn(lane):
        with jax.profiler.TraceAnnotation("be.hbm"):
            return float(be_hbm(x0, w))

    # the regulator charges what a full-size quantum moves, at any size
    return fn, float(PASSES * LAYERS * WIDTH * WIDTH * 2)

