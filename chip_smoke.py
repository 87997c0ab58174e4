#!/usr/bin/env python3
"""Quickest proof that the RT-Gang executor's main path runs on a TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # one host with four chips

One chip, three phases:
  serve    minitron-4b at full width in bfloat16 through
           ``repro.launch.serve.serve``: four seeded requests, the decode step
           as the RT gang on ``GangExecutor`` beside an HBM-heavy best-effort
           co-runner; the generated tokens are checked against the greedy
           argmax of a full prefill of the same prefix.
  dave2    the paper's DAVE-2 gang through the ``benchmarks/fig6_dnn_cdf``
           executor path, in Co-Sched and RT-Gang modes.
  kernels  every Pallas kernel compiled for the chip (``interpret=False``) at
           the widths in ``repro.kernels.cases``, against its reference.

``--chips 4`` runs only the sharded phase: a few AdamW steps of internvl2-1b
at full width through ``repro.launch.train.train``, FSDP over four chips,
against the same seeded steps on one device of the same process.

The last line printed is ``{"ok": true, "device": {...}}``. A failed phase,
or a backend other than TPU, exits non-zero before it. Everything runs in
this one process, because the chip belongs to the process that opened it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

SERVE_ARCH = "minitron-4b"
TRAIN_ARCH = "internvl2-1b"
# tolerance of tests/test_sharding.py::test_dense_distributed_matches_single
LOSS_RTOL = 2e-3


class PhaseFailed(RuntimeError):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def device_phase(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise PhaseFailed(f"no TPU found: JAX reports {len(devs)} "
                          f"{d.platform} device(s) ({d.device_kind})")
    require(len(devs) >= chips, f"--chips {chips} needs {chips} TPU "
            f"devices, JAX reports {len(devs)}")
    log("device", f"platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def serve_phase() -> None:
    import jax
    import numpy as np
    from repro.launch.serve import serve

    # four requests of 32 prompt tokens on 4 slots x 256 (serve's constants)
    n_req, max_new = 4, 16
    t0 = time.perf_counter()
    out = serve(SERVE_ARCH, full_size=True, n_requests=n_req,
                max_new=max_new, gang=True, duration=4.0)
    reqs, stats = out["requests"], out["stats"]
    be = stats["be_quanta"].get("bg-batch", 0)
    lat = np.asarray(out["decode_ms"])
    log("serve", f"{SERVE_ARCH} bf16: {sum(r.done for r in reqs)}/{n_req} "
        f"requests done, decode_steps={out['decode_steps']}, best-effort "
        f"quanta={be}, wall {time.perf_counter() - t0:.1f}s incl. init and "
        f"compile")
    require(all(r.done and len(r.out) == max_new for r in reqs),
            f"unfinished requests: {[len(r.out) for r in reqs]}")
    require(out["decode_steps"] > 0, "no decode step ran")
    require(be > 0, "no best-effort quantum ran")
    require(len(lat) > 0, "no decode quantum was timed")
    log("serve", f"decode quantum ms p50={np.percentile(lat, 50):.3f} "
        f"p99={np.percentile(lat, 99):.3f} n={len(lat)} (informational)")

    # reference: greedy argmax of a full prefill over the same prefix, at
    # the first decode step and the last; bf16 decode (KV cache) and
    # prefill (masked attention) round differently, so a token passes
    # when its reference logit is within 2% of the logit range of the top
    engine = out["engine"]
    prefill = jax.jit(engine.api.prefill_fn)
    vocab = engine.api.cfg.vocab_size
    for r in reqs:
        require(all(0 <= t < vocab for t in r.out), f"request {r.rid}: "
                f"token out of vocabulary")
    r = reqs[0]
    for k in (1, max_new - 1):
        toks = np.concatenate([r.prompt, np.asarray(r.out[:k], np.int32)])
        logits, _ = prefill(engine.params, {"tokens": toks[None]})
        ref = np.asarray(logits[0, -1], np.float32)
        require(np.isfinite(ref).all(), "non-finite prefill logits")
        top, tok = ref.max(), r.out[k]
        slack = 0.02 * (top - ref.min())
        log("serve", f"request 0 token {k}: engine {tok} (ref logit "
            f"{ref[tok]:.4f}), reference argmax {int(ref.argmax())} "
            f"({top:.4f})")
        require(ref[tok] >= top - slack, f"token {k} disagrees with the "
                f"prefill reference")
    mem = jax.devices()[0].memory_stats() or {}
    log("serve", f"peak HBM bytes in use: "
        f"{mem.get('peak_bytes_in_use', 'not reported')}")


def dave2_phase() -> None:
    from benchmarks import fig6_dnn_cdf

    res = fig6_dnn_cdf.run(duration=2.0)
    for mode, row in res.items():
        log("dave2", f"{mode}: {row}")
    for mode in ("cosched", "rtgang"):
        require(res[mode].get("n", 0) > 0,
                f"DAVE-2 gang completed no release under {mode}")


def kernel_phase() -> None:
    import jax
    import numpy as np
    from repro.kernels.cases import CASES

    failed = []
    for name, case in CASES.items():
        args = jax.jit(case.inputs)(jax.random.key(0))
        out = jax.jit(lambda *a: case.run(*a, interpret=False))(*args)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(case.ref)(*args)
        worst, ok = 0.0, True
        for o, r in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
            o = np.asarray(o, np.float32)
            r = np.asarray(r, np.float32)
            require(o.shape == r.shape, f"{name}: shape {o.shape} != "
                    f"{r.shape}")
            err = np.abs(o - r)
            worst = max(worst, float(err.max()))
            ok &= bool(np.isfinite(o).all()) and bool(
                (err <= case.atol + case.rtol * np.abs(r)).all())
        log("kernels", f"{name} ({case.source}): max |err| {worst:.3e}, "
            f"{'allclose' if ok else 'NOT allclose'} (atol={case.atol}, "
            f"rtol={case.rtol})")
        if not ok:
            failed.append(name)
    require(not failed, f"kernels off their references: {failed}")


def sharded_train_phase() -> None:
    import tempfile

    import jax
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import train

    losses = {}
    for name, n_dev in (("fsdp4", 4), ("one-device", 1)):
        with tempfile.TemporaryDirectory() as ckpt:
            hist, state = train(TRAIN_ARCH, full_size=True, steps=3,
                                batch=4, seq=512, ckpt_dir=ckpt,
                                ckpt_every=1000,
                                mesh=make_local_mesh(n_dev, 1))
        params = jax.tree.leaves(state["params"])
        total = sum(x.nbytes for x in params)
        per_dev: dict = {}
        for x in params:
            for s in x.addressable_shards:
                per_dev[s.device] = per_dev.get(s.device, 0) + s.data.nbytes
        most = max(per_dev.values())
        losses[name] = [m["loss"] for m in hist]
        log("train", f"{name}: losses {losses[name]}; param bytes total "
            f"{total}, most on one device {most} ({most / total:.4f})")
        if n_dev == 4:
            require(len(per_dev) == 4, f"params on {len(per_dev)} devices")
            require(most < 0.3 * total, "params are not FSDP-sharded")
        del state, params
    for s, d in zip(losses["one-device"], losses["fsdp4"]):
        require(abs(d - s) < LOSS_RTOL * max(1.0, abs(s)),
                f"sharded loss {d} != one-device loss {s}")
    require(len(losses["fsdp4"]) == 3, "training did not take 3 steps")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the FSDP training phase on four chips")
    args = ap.parse_args()
    try:
        device = device_phase(args.chips)
        if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
            raise PhaseFailed(f"no repro sources next to {__file__}")
        sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
        from repro.launch.cache import enable_compile_cache
        log("cache", enable_compile_cache())
        phases = [sharded_train_phase] if args.chips == 4 else \
            [serve_phase, dave2_phase, kernel_phase]
        for phase in phases:
            t0 = time.perf_counter()
            phase()
            log(phase.__name__, f"passed in {time.perf_counter() - t0:.1f}s")
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
