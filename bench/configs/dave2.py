"""Glue for ``dave2``: the paper's DeepPicar perception-to-control loop as
an RT gang of the program's DAVE-2 (``models/dave2.py``).

Each release hands the device a batch of new seeded camera frames, one
from each camera of the rig the traffic mix names (``frames_per_release``),
runs one forward pass over them at the float32 precision the configuration
states, and waits for the steering outputs. The weights come from the seed
through the reference's generator. After the window a seeded sample of the
releases' outputs is compared with the plain float64 reference on the same
frames.
"""
from __future__ import annotations

import gc
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import generate
from bench.configs import dave2_ref as ref

SPANS = ("dave2.forward",)              # host spans the trace reduction reads


def model_flops(spec: dict) -> float:
    """Operations of one forward pass, from shapes: 2 per multiply-add of
    each convolution and dense layer."""
    total = 0.0
    h, w = spec["input_hw"]
    c_in = spec["in_channels"]
    for c_out, k, s in spec["conv"]:
        h, w = (h - k) // s + 1, (w - k) // s + 1
        total += 2.0 * h * w * k * k * c_in * c_out
        c_in = c_out
    dims = [h * w * c_in] + list(spec["fc"]) + [spec["n_outputs"]]
    for a, b in zip(dims[:-1], dims[1:]):
        total += 2.0 * a * b
    return total


class Cell:
    def __init__(self, spec: dict, mix: dict, seed: int, seconds: float,
                 small: bool = False, fault: str = None):
        from repro.configs.deeppicar import Dave2Config
        from repro.models.dave2 import dave2_apply

        self.spec, self.seed = spec, seed
        cfg = Dave2Config(input_hw=tuple(spec["input_hw"]),
                          in_channels=spec["in_channels"],
                          conv=tuple(tuple(c) for c in spec["conv"]),
                          fc=tuple(spec["fc"]), n_outputs=spec["n_outputs"])
        precision = spec["matmul_precision"]

        @jax.jit
        def dave2_forward(params, frame):
            with jax.default_matmul_precision(precision):
                x = frame.astype(jnp.float32) / 255.0
                return dave2_apply(cfg, params, x)[:, 0]

        self._forward = dave2_forward
        self.params = ref.make_params(spec, seed)
        self.batch = mix["frames_per_release"]
        n = int(math.ceil(seconds / (mix["period_ms"] / 1e3))) + 2
        self.frames = generate.frames(n * self.batch, spec["input_hw"],
                                      spec["in_channels"], seed)
        self.outputs: Dict[int, np.ndarray] = {}
        self.quantum_flops: List[float] = []
        self._flops = model_flops(spec) * self.batch
        self.fault = fault
        if fault not in (None, "answer"):
            raise ValueError(f"no fault {fault!r} for dave2")

        self.n_lanes = spec["lanes"]
        self.rt_lanes = tuple(spec["rt_lanes"])
        self.rt_prio = spec["rt_prio"]
        self.period_s = mix["period_ms"] / 1e3
        self.regulation_s = spec["regulation_ms"] / 1e3
        self.be_lanes = tuple(spec["be_lanes"])
        self.be_budget_bytes = float(spec["be_budget_bytes"])

    def release_frames(self, idx: int) -> np.ndarray:
        """The frames of release ``idx``: one from each camera."""
        k = idx % (len(self.frames) // self.batch)
        return self.frames[k * self.batch:(k + 1) * self.batch]

    def warm(self):
        np.asarray(self._forward(self.params,
                                 jnp.asarray(self.release_frames(0))))

    def quantum(self, lane: int, idx: int):
        with jax.profiler.TraceAnnotation("rt.quantum"):
            with jax.profiler.TraceAnnotation("dave2.forward"):
                y = np.asarray(self._forward(
                    self.params, jnp.asarray(self.release_frames(idx))))
        if self.fault == "answer" and idx - 1 in self.outputs:
            y = self.outputs[idx - 1]       # a stale steering command
        self.outputs[idx] = y
        self.quantum_flops.append(self._flops)

    def finish(self) -> Dict:
        out = {"outputs": dict(self.outputs)}
        self.params = None
        gc.collect()
        return out

    def check(self, out: Dict, limits: Dict, control: bool = False):
        """Numbers compared, each ``(value, limit, op)``: over a seeded
        sample of the releases, the widest and the root-mean-square error
        of every steering output of those releases against the reference
        on the same frames, in the output's own units. With ``control`` the
        control's outputs on those frames stand in the program's place."""
        outputs = out["outputs"]
        idx = sorted(outputs)
        r = generate.rng(self.seed, 52)
        take = sorted(r.permutation(idx)[:limits["sample_releases"]]) \
            if idx else []
        err = {"steer_err_max": float("inf"), "steer_err_rms": float("inf")}
        if take:
            params = ref.make_params(self.spec, self.seed)
            frames = np.concatenate([self.release_frames(i) for i in take])
            want = ref.forward(self.spec, params, frames)[:, 0]
            if control:
                got = ref.forward(self.spec, params, frames, control=True)[:, 0]
            else:
                got = np.concatenate([outputs[i] for i in take]
                                     ).astype(np.float64)
            err = _errors(got, want)
        checks = {k: (v, limits[k], "<=") for k, v in err.items()}
        checks["frames_checked"] = (len(take) * self.batch,
                                    limits["frames_checked"], ">=")
        return checks


def _errors(got: np.ndarray, want: np.ndarray) -> Dict[str, float]:
    d = got - want
    return {"steer_err_max": float(np.abs(d).max()),
            "steer_err_rms": float(np.sqrt(np.mean(d ** 2)))}


def build(spec, mix, seed, seconds, small=False, fault=None):
    return Cell(spec, mix, seed, seconds, small=small, fault=fault)
