"""Serving launcher: gang-scheduled serving of a latency-critical model with
best-effort background work — the paper's deployment story end-to-end.

``python -m repro.launch.serve --arch qwen2-7b --requests 6 [--full-size]``

The decode step of the served model is the RT gang (priority 10); a
background batch job (synthetic compute) is best-effort, throttled by the
gang's byte budget. Compare p99 decode latency with --no-gang.

By default the model is the reduced float32 smoke config; ``--full-size``
serves the published config with bfloat16 params and compute, and swaps the
background job for an HBM-heavy bfloat16 matmul chain.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.configs.base import ParallelConfig
from repro.core.executor import BEJob, GangExecutor, RTJob
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models.model import build_model
from repro.serving.engine import Request, ServingEngine

DECODE_PERIOD_S = 0.01
MAX_BATCH = 4                # decode slots
MAX_SEQ = 256                # cache length per slot
PROMPT_LEN = 32
SEED = 0                     # weights, prompts


def background_job(full_size: bool):
    """The best-effort co-runner: ``(fn, bytes one quantum moves)``."""
    if not full_size:
        x = jnp.ones((512, 512), jnp.float32)
        mm = jax.jit(lambda x: (x @ x.T).sum())
        return (lambda lane: float(mm(x))), 1e6
    # stream a 512 MiB bf16 weight stack through the MXU four times: each
    # layer reads 32 MiB for 2 GFLOP, so one quantum is bound by HBM
    w = jax.random.normal(jax.random.key(1), (16, 4096, 4096),
                          jnp.bfloat16) * 0.02
    x0 = jnp.ones((64, 4096), jnp.bfloat16)
    passes = 4

    @jax.jit
    def chain(x, w):
        def layer(h, wi):
            return jnp.tanh(h @ wi), None

        def sweep(_, h):
            return jax.lax.scan(layer, h, w)[0]
        return jax.lax.fori_loop(0, passes, sweep, x).astype(jnp.float32).sum()

    return (lambda lane: float(chain(x0, w))), float(passes * w.nbytes)


def serve(arch: str = "qwen2-7b", *, full_size: bool = False,
          n_requests: int = 6, max_new: int = 16, gang: bool = True,
          duration: float = 6.0) -> dict:
    """Serve ``n_requests`` seeded requests of ``PROMPT_LEN`` tokens for
    ``duration`` seconds on ``MAX_BATCH`` slots of ``MAX_SEQ`` tokens, with
    the decode step as the RT gang and one best-effort co-runner.

    Returns the requests, the engine (its model and params), its
    decode-step count, the wall time of every decode quantum that stepped
    the batch (ms), and the executor's stats. Raises whatever a quantum
    raised."""
    cfg = get_config(arch)
    if full_size:
        parallel = ParallelConfig(param_dtype="bfloat16",
                                  compute_dtype="bfloat16")
    else:
        cfg = reduced(cfg)
        parallel = ParallelConfig(param_dtype="float32",
                                  compute_dtype="float32",
                                  q_block=64, kv_block=64)
    api = build_model(cfg, parallel, make_local_mesh(1, 1))
    params = api.init(jax.random.key(SEED))
    engine = ServingEngine(api, params, max_batch=MAX_BATCH, max_seq=MAX_SEQ)
    engine.warmup(prompt_len=PROMPT_LEN)

    rng = np.random.default_rng(SEED)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=(PROMPT_LEN,))
                    .astype(np.int32),
                    max_new=max_new)
            for i in range(n_requests)]
    pending = list(reqs)
    be_fn, be_bytes = background_job(full_size)
    be_fn(0)                                    # compile outside the run
    decode_ms = []

    def decode_quantum(lane, idx):
        t0 = time.perf_counter()
        while pending and engine.add_request(pending[0]):
            pending.pop(0)
        steps = engine.decode_steps
        engine.decode_step()                    # syncs on the next tokens
        if engine.decode_steps > steps:
            decode_ms.append((time.perf_counter() - t0) * 1e3)

    ex = GangExecutor(n_lanes=2, enabled=gang, regulation_interval_s=0.02)
    # the gang admits two background quanta per regulation window
    ex.submit_rt(RTJob(name="decode", fn=decode_quantum, lanes=(0,),
                       prio=10, period_s=DECODE_PERIOD_S,
                       budget_bytes=2 * be_bytes,
                       n_jobs=int(duration / DECODE_PERIOD_S)))
    ex.submit_be(BEJob(name="bg-batch", fn=be_fn, lanes=(0, 1),
                       bytes_per_quantum=be_bytes))
    stats = ex.run(duration)
    return {"requests": reqs, "engine": engine,
            "decode_steps": engine.decode_steps,
            "decode_ms": decode_ms, "stats": stats}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--full-size", action="store_true",
                    help="serve the full config in bfloat16 (default: "
                         "reduced float32 smoke size)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--no-gang", action="store_true")
    ap.add_argument("--duration", type=float, default=6.0)
    args = ap.parse_args()

    enable_compile_cache()
    out = serve(args.arch, full_size=args.full_size,
                n_requests=args.requests, max_new=args.max_new,
                gang=not args.no_gang, duration=args.duration)
    reqs = out["requests"]
    done = sum(r.done for r in reqs)
    print(f"[serve] gang={'off' if args.no_gang else 'on'} "
          f"requests done {done}/{len(reqs)} "
          f"decode_steps={out['decode_steps']}")
    lat = np.asarray(out["decode_ms"])
    if len(lat):
        print(f"[serve] decode quantum ms: "
              f"p50={np.percentile(lat, 50):.2f} "
              f"p99={np.percentile(lat, 99):.2f} max={lat.max():.2f}")
    print(f"[serve] best-effort quanta: {out['stats']['be_quanta']}")


if __name__ == "__main__":
    main()
