"""Launcher entry points: the serving path at smoke size, and where the
persistent compilation cache lives."""
import os
import pathlib
import subprocess
import sys

import numpy as np

from repro.launch.cache import CHECKOUT_CACHE
from repro.launch.serve import serve

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_serve_reduced_runs_requests_under_the_gang():
    out = serve("qwen2-7b", n_requests=3, max_new=4, duration=2.0)
    assert all(r.done and len(r.out) == 4 for r in out["requests"])
    assert out["decode_steps"] >= 3
    assert len(out["decode_ms"]) == out["decode_steps"]
    assert out["stats"]["be_quanta"]["bg-batch"] > 0
    assert np.all(np.asarray(out["decode_ms"]) > 0)


_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


def _probe(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE.format(compile=compile_)], env=env,
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.split()[:2]


def test_compile_cache_follows_env_var(tmp_path):
    cache = tmp_path / "jax-cache"
    assert _probe(cache, True) == [str(cache), str(cache)]
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_fixed_checkout_path():
    assert CHECKOUT_CACHE == ROOT / ".jax_cache"
    assert _probe(None, False) == [str(CHECKOUT_CACHE)] * 2
