"""Optimizers built from scratch: AdamW and Adafactor (factored 2nd moment).

State sharding mirrors parameter sharding (derived from the same logical
axes), so FSDP shards optimizer state for free. ``opt_state_dtype`` allows
bf16 moments for the trillion-param configs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


@dataclasses.dataclass(frozen=True)
class OptConfig:
    name: str = "adamw"              # adamw | adafactor | sgd
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.01
    grad_clip: float = 1.0
    state_dtype: str = "float32"
    warmup: int = 100
    decay_steps: int = 10_000
    min_lr_frac: float = 0.1


def lr_at(cfg: OptConfig, step):
    step = step.astype(jnp.float32) if hasattr(step, "astype") else float(step)
    warm = jnp.minimum(1.0, (step + 1) / max(cfg.warmup, 1))
    prog = jnp.clip((step - cfg.warmup) / max(cfg.decay_steps - cfg.warmup, 1),
                    0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    frac = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos
    return cfg.lr * warm * frac


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm):
    g = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(g, 1e-9))
    return jax.tree.map(lambda x: (x.astype(jnp.float32) * scale).astype(
        x.dtype), grads), g


class Optimizer:
    """(init, update) pair; functional, pytree state."""

    def __init__(self, cfg: OptConfig):
        self.cfg = cfg

    # ---- adamw -------------------------------------------------------------
    def _adamw_init(self, params):
        dt = DTYPES[self.cfg.state_dtype]
        z = lambda p: jnp.zeros_like(p, dtype=dt)   # keeps p's sharding
        return {"m": jax.tree.map(z, params), "v": jax.tree.map(z, params),
                "count": jnp.zeros((), jnp.int32)}

    def _adamw_update(self, grads, state, params):
        c = self.cfg
        cnt = state["count"] + 1
        lr = lr_at(c, cnt)
        b1c = 1 - c.b1 ** cnt.astype(jnp.float32)
        b2c = 1 - c.b2 ** cnt.astype(jnp.float32)

        def upd(g, m, v, p):
            g32 = g.astype(jnp.float32)
            m_new = c.b1 * m.astype(jnp.float32) + (1 - c.b1) * g32
            v_new = c.b2 * v.astype(jnp.float32) + (1 - c.b2) * g32 * g32
            step = (m_new / b1c) / (jnp.sqrt(v_new / b2c) + c.eps)
            if p.ndim >= 2:
                step = step + c.weight_decay * p.astype(jnp.float32)
            p_new = p.astype(jnp.float32) - lr * step
            return p_new.astype(p.dtype), m_new.astype(m.dtype), \
                v_new.astype(v.dtype)

        out = jax.tree.map(upd, grads, state["m"], state["v"], params)
        p_new = jax.tree.map(lambda t: t[0], out,
                             is_leaf=lambda t: isinstance(t, tuple))
        m_new = jax.tree.map(lambda t: t[1], out,
                             is_leaf=lambda t: isinstance(t, tuple))
        v_new = jax.tree.map(lambda t: t[2], out,
                             is_leaf=lambda t: isinstance(t, tuple))
        return p_new, {"m": m_new, "v": v_new, "count": cnt}

    # ---- adafactor ----------------------------------------------------------
    def _adafactor_init(self, params):
        dt = DTYPES[self.cfg.state_dtype]

        def st(p):
            if p.ndim >= 2:
                return {"vr": jnp.zeros(p.shape[:-1], dt),
                        "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], dt)}
            return {"v": jnp.zeros(p.shape, dt)}

        return {"f": jax.tree.map(st, params),
                "count": jnp.zeros((), jnp.int32)}

    def _adafactor_update(self, grads, state, params):
        c = self.cfg
        cnt = state["count"] + 1
        lr = lr_at(c, cnt)
        beta = 1.0 - (cnt.astype(jnp.float32) + 1) ** -0.8

        def upd(g, f, p):
            g32 = jnp.square(g.astype(jnp.float32)) + 1e-30
            if p.ndim >= 2:
                vr = beta * f["vr"].astype(jnp.float32) + (1 - beta) * \
                    g32.mean(axis=-1)
                vc = beta * f["vc"].astype(jnp.float32) + (1 - beta) * \
                    g32.mean(axis=-2)
                denom = (vr[..., None] * vc[..., None, :]
                         / jnp.maximum(vr.mean(axis=-1)[..., None, None], 1e-30))
                step = g.astype(jnp.float32) / (jnp.sqrt(denom) + 1e-12)
                newf = {"vr": vr.astype(f["vr"].dtype),
                        "vc": vc.astype(f["vc"].dtype)}
            else:
                v = beta * f["v"].astype(jnp.float32) + (1 - beta) * g32
                step = g.astype(jnp.float32) / (jnp.sqrt(v) + 1e-12)
                newf = {"v": v.astype(f["v"].dtype)}
            # relative step clipping (Shazeer & Stern)
            rms = jnp.sqrt(jnp.mean(jnp.square(step)))
            step = step / jnp.maximum(1.0, rms)
            if p.ndim >= 2:
                step = step + c.weight_decay * p.astype(jnp.float32)
            p_new = p.astype(jnp.float32) - lr * step
            return p_new.astype(p.dtype), newf

        is_state = lambda x: isinstance(x, dict) and ("vr" in x or "v" in x)
        out = jax.tree.map(upd, grads, state["f"], params,
                           is_leaf=lambda x: is_state(x))
        p_new = jax.tree.map(lambda t: t[0], out,
                             is_leaf=lambda t: isinstance(t, tuple))
        f_new = jax.tree.map(lambda t: t[1], out,
                             is_leaf=lambda t: isinstance(t, tuple))
        return p_new, {"f": f_new, "count": cnt}

    # ---- sgd ---------------------------------------------------------------
    def _sgd_init(self, params):
        return {"count": jnp.zeros((), jnp.int32)}

    def _sgd_update(self, grads, state, params):
        lr = lr_at(self.cfg, state["count"] + 1)
        p_new = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32)
                          - lr * g.astype(jnp.float32)).astype(p.dtype),
            params, grads)
        return p_new, {"count": state["count"] + 1}

    # ---- public -------------------------------------------------------------
    def init(self, params):
        return getattr(self, f"_{self.cfg.name}_init")(params)

    def update(self, grads, state, params):
        if self.cfg.grad_clip > 0:
            grads, gnorm = clip_by_global_norm(grads, self.cfg.grad_clip)
        return getattr(self, f"_{self.cfg.name}_update")(grads, state, params)

    # ---- sharding of state ---------------------------------------------------
    def state_pspecs(self, param_pspecs, param_shapes):
        from jax.sharding import PartitionSpec as P
        if self.cfg.name == "adamw":
            return {"m": param_pspecs, "v": param_pspecs, "count": P()}
        if self.cfg.name == "adafactor":
            def st(spec, shape):
                dims = len(shape.shape if hasattr(shape, "shape") else shape)
                parts = list(spec) + [None] * (dims - len(spec))
                if dims >= 2:
                    return {"vr": P(*parts[:-1]),
                            "vc": P(*(parts[:-2] + parts[-1:]))}
                return {"v": P(*parts)}
            return {"f": jax.tree.map(st, param_pspecs, param_shapes,
                                      is_leaf=lambda x: isinstance(x, P)),
                    "count": P()}
        return {"count": P()}
