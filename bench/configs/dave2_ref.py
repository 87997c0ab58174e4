"""Plain reference for the ``dave2`` configuration, independent of the
program: its weights drawn from the seed, and its forward pass in numpy
float64 (valid convolutions by patches, tanh after each convolution and
each hidden dense layer, a linear steering output).

The control is the same pass one precision step below the configuration's
``matmul_precision`` (``high``, three bfloat16 passes per product): one
bfloat16 pass, each operand of every product rounded to bfloat16 and the
products summed in float64. It is written out here so that it reads the
same on any host.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from bench.generate import jax_key


def shapes(spec: dict) -> dict:
    """Leaf shapes, in the layout the program's params take."""
    out = {}
    h, w = spec["input_hw"]
    c_in = spec["in_channels"]
    for i, (c_out, k, s) in enumerate(spec["conv"]):
        out[f"conv{i}_w"] = (k, k, c_in, c_out)
        out[f"conv{i}_b"] = (c_out,)
        h, w, c_in = (h - k) // s + 1, (w - k) // s + 1, c_out
    dims = [h * w * c_in] + list(spec["fc"]) + [spec["n_outputs"]]
    for i in range(len(dims) - 1):
        out[f"fc{i}_w"] = (dims[i], dims[i + 1])
        out[f"fc{i}_b"] = (dims[i + 1],)
    return out


def make_params(spec: dict, seed: int):
    """Every weight from ``seed`` in one program on the device: normal with
    std 1/sqrt(fan-in) (every dimension but the last: a convolution's
    window times its input channels), biases 0."""
    shp = shapes(spec)
    names = sorted(shp)

    @jax.jit
    def dave2_weights(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, n in zip(keys, names):
            s = shp[n]
            out[n] = (jnp.zeros(s, jnp.float32) if n.endswith("_b") else
                      jax.random.normal(k, s, jnp.float32)
                      / math.sqrt(math.prod(s[:-1])))
        return out

    return dave2_weights(jax_key(seed, 41))


def _patches(x, k, s):
    """x: (N, H, W, C) -> (N, Ho, Wo, k*k*C) in (kh, kw, c) order."""
    n, h, w, c = x.shape
    ho, wo = (h - k) // s + 1, (w - k) // s + 1
    cols = [x[:, i:i + s * ho:s, j:j + s * wo:s, :]
            for i in range(k) for j in range(k)]
    return np.concatenate(cols, axis=-1).reshape(n, ho, wo, k * k * c)


def _bf16(x):
    return x.astype(ml_dtypes.bfloat16).astype(np.float64)


def _bf16_matmul(a, w):
    """The control's product one step below ``high``: one bfloat16 pass."""
    return _bf16(a) @ _bf16(w)


CONTROL = {"high": _bf16_matmul}
BLOCK = 64                      # frames per block: bounds the host memory


def forward(spec: dict, params: dict, frames: np.ndarray,
            control: bool = False) -> np.ndarray:
    """frames: (N, H, W, C) uint8 -> steering (N, n_outputs) in float64,
    in blocks of frames; with ``control`` every product one precision step
    below the configuration's."""
    mm = CONTROL[spec["matmul_precision"]] if control else np.matmul
    p = {k: np.asarray(v).astype(np.float64) for k, v in params.items()}
    return np.concatenate([_forward(spec, p, frames[i:i + BLOCK], mm)
                           for i in range(0, len(frames), BLOCK)])


def _forward(spec, p, frames, mm):
    x = frames.astype(np.float64) / 255.0
    for i, (c_out, k, s) in enumerate(spec["conv"]):
        cols = _patches(x, k, s)
        w = p[f"conv{i}_w"].reshape(-1, c_out)
        x = np.tanh(mm(cols, w) + p[f"conv{i}_b"])
    x = x.reshape(x.shape[0], -1)
    n_fc = len(spec["fc"]) + 1
    for i in range(n_fc):
        x = mm(x, p[f"fc{i}_w"]) + p[f"fc{i}_b"]
        if i < n_fc - 1:
            x = np.tanh(x)
    return x
