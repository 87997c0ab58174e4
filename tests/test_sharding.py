"""Distributed-correctness tests: run a subprocess with 8 forced host
devices and check (a) sharded loss == single-device loss for dense and MoE
(exercising FSDP gathers, TP constraints, the shard_map MoE all-to-all path),
and (b) the trip-count-aware collective accounting sees real collectives.

A subprocess is required because jax fixes the device count at first init.
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.configs.base import ParallelConfig, MoEConfig
from repro.launch.mesh import make_local_mesh
from repro.models.model import build_model
from repro.roofline.hlo_analysis import analyze

out = {}
for arch in ["qwen2-7b", "olmoe-1b-7b"]:
    cfg = reduced(get_config(arch))
    if cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=MoEConfig(n_experts=8, top_k=2,
                                                     capacity_factor=8.0))
    rng = np.random.default_rng(0)
    B, S = 4, 32
    batch = {"tokens": jnp.asarray(rng.integers(1, cfg.vocab_size,
                                                size=(B, S)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                                size=(B, S)), jnp.int32)}

    losses = {}
    hlo_stats = {}
    for name, (d, m) in {"single": (1, 1), "dist": (2, 4)}.items():
        mesh = make_local_mesh(d, m)
        par = ParallelConfig(param_dtype="float32", compute_dtype="float32",
                             q_block=8, kv_block=8,
                             sequence_parallel=(name == "dist"))
        api = build_model(cfg, par, mesh)
        params = api.init(jax.random.key(0))
        with mesh:
            c = jax.jit(lambda p, b: api.loss_fn(p, b)[0]).lower(
                params, batch).compile()
            losses[name] = float(c(params, batch))
            hlo_stats[name] = analyze(c.as_text())
    out[arch] = {
        "single": losses["single"], "dist": losses["dist"],
        "dist_collective_bytes": hlo_stats["dist"]["collective_total"],
        "single_collective_bytes": hlo_stats["single"]["collective_total"],
    }
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def dist_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def test_dense_distributed_matches_single(dist_result):
    r = dist_result["qwen2-7b"]
    assert abs(r["dist"] - r["single"]) < 2e-3 * max(1.0, abs(r["single"]))


def test_moe_distributed_matches_single(dist_result):
    """shard_map EP all-to-all path == dense fallback (no drops)."""
    r = dist_result["olmoe-1b-7b"]
    assert abs(r["dist"] - r["single"]) < 5e-3 * max(1.0, abs(r["single"]))


def test_distributed_run_has_collectives(dist_result):
    for arch in ("qwen2-7b", "olmoe-1b-7b"):
        r = dist_result[arch]
        assert r["dist_collective_bytes"] > 0
        assert r["single_collective_bytes"] == 0
