"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs a (reduced or full) config on the local mesh with the full substrate:
sharded data loading, FSDP/TP sharding, checkpoint/restart (use
--fail-at-step to watch the restart path recover deterministically).
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Optional

import jax

from repro.configs import get_config, reduced
from repro.configs.base import ParallelConfig
from repro.data.pipeline import DataConfig
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models.model import build_model
from repro.training.optimizer import OptConfig, Optimizer
from repro.training.runner import RunnerConfig, SimulatedFailure, TrainRunner


def train(arch: str = "qwen2-7b", *, full_size: bool = False,
          steps: int = 60, batch: int = 8, seq: int = 128, lr: float = 3e-3,
          ckpt_dir: str = "/tmp/repro_ckpt", ckpt_every: int = 20,
          fail_at_step: Optional[int] = None, data: Optional[str] = None,
          mesh=None):
    """Train ``arch`` with params and optimizer state FSDP-sharded over
    ``mesh`` (default: every local device on ``data``); a simulated
    failure restarts once from the latest checkpoint. Returns
    ``(metrics_log, final_state)``."""
    cfg = get_config(arch)
    if not full_size:
        cfg = reduced(cfg)
    if mesh is None:
        mesh = make_local_mesh(len(jax.devices()), 1)
    parallel = ParallelConfig(param_dtype="float32", compute_dtype="float32",
                              q_block=64, kv_block=64)
    api = build_model(cfg, parallel, mesh)
    opt = Optimizer(OptConfig(name="adamw", lr=lr, warmup=10,
                              decay_steps=max(steps, 20)))
    data_cfg = DataConfig(
        seq_len=seq, global_batch=batch,
        vocab_size=cfg.vocab_size, path=data,
        n_vision_tokens=cfg.n_vision_tokens, d_model=cfg.d_model,
        n_frames=cfg.n_encoder_frames if cfg.family == "audio" else 0)
    rc = RunnerConfig(total_steps=steps, ckpt_every=ckpt_every,
                      ckpt_dir=ckpt_dir, fail_at_step=fail_at_step)
    runner = TrainRunner(api, opt, data_cfg, rc)
    try:
        state = runner.run()
    except SimulatedFailure as e:
        print(f"[ft] {e}; restarting from latest checkpoint...")
        runner2 = TrainRunner(api, opt, data_cfg,
                              dataclasses.replace(rc, fail_at_step=None))
        state = runner2.run()
        runner.metrics_log.extend(runner2.metrics_log)
    return runner.metrics_log, state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (default: reduced smoke size)")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--fail-at-step", type=int, default=None)
    ap.add_argument("--data", default=None, help="memmapped token file")
    args = ap.parse_args()

    enable_compile_cache()
    log, _ = train(args.arch, full_size=args.full_size, steps=args.steps,
                   batch=args.batch, seq=args.seq, lr=args.lr,
                   ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                   fail_at_step=args.fail_at_step, data=args.data)
    first = log[0]["loss"] if log else None
    last = log[-1]["loss"] if log else None
    print(f"[train] {args.arch}: steps={len(log)} "
          f"loss {first:.4f} -> {last:.4f}")


if __name__ == "__main__":
    main()
