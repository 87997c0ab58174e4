"""Batched serving engine with slot-based continuous batching and RT-Gang
integration.

The engine mirrors the paper's deployment story: the *decode step* of a
latency-critical model is the real-time gang (it must meet a control-loop
deadline, like the paper's DNN steering task); prefills of newly-arrived
requests and any background jobs are best-effort work that RT-Gang throttles.

Slots: a fixed decode batch of B slots, each with its own cache position;
``decode_fn`` already takes per-slot positions, so slot refill is just a
batch-dim ``dynamic_update_slice`` of the prefilled KV into the live cache.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import ModelApi


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray               # (S,) int32
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    done: bool = False


class ServingEngine:
    def __init__(self, api: ModelApi, params, *, max_batch: int,
                 max_seq: int, greedy: bool = True):
        self.api = api
        self.params = params
        self.B = max_batch
        self.S = max_seq
        cfg = api.cfg
        cd = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        self.cache = self._empty_cache(cd)
        self.pos = jnp.zeros((max_batch,), jnp.int32)
        self.tokens = jnp.zeros((max_batch, 1), jnp.int32)
        self.active = np.zeros((max_batch,), bool)
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self._decode = jax.jit(api.decode_fn, donate_argnums=(1,))
        self._prefill = jax.jit(api.prefill_fn)
        self.greedy = greedy
        self.decode_steps = 0

    def _empty_cache(self, cd):
        cfg = self.api.cfg
        assert cfg.family in ("dense", "vlm", "moe"), \
            "slot engine currently serves attention-cache families"
        L = cfg.n_layers
        shp = (L, self.B, self.S, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shp, cd), "v": jnp.zeros(shp, cd)}

    # ------------------------------------------------------------------
    def warmup(self, prompt_len: int):
        """Compile everything a serving step runs, then reset to fresh
        state (the decode cache is donated, so no snapshot/restore): the
        prefill, the insertion into every slot, and two decode steps — the
        second takes the cache the first returned, whose placement differs
        from the fresh cache's and so compiles its own program."""
        for i in range(self.B):
            self.add_request(Request(
                rid=-1 - i, prompt=np.zeros((prompt_len,), np.int32),
                max_new=3))
        self.decode_step()
        self.decode_step()
        self.cache = self._empty_cache(self.cache["k"].dtype)
        self.pos = jnp.zeros((self.B,), jnp.int32)
        self.tokens = jnp.zeros((self.B, 1), jnp.int32)
        self.active = np.zeros((self.B,), bool)
        self.slot_req = [None] * self.B
        self.decode_steps = 0

    def add_request(self, req: Request) -> bool:
        free = [i for i in range(self.B) if not self.active[i]]
        if not free:
            return False
        slot = free[0]
        S_p = req.prompt.shape[0]
        batch = {"tokens": jnp.asarray(req.prompt[None, :], jnp.int32)}
        logits, cache = self._prefill(self.params, batch)
        # insert prefilled KV into the live cache at this slot
        k = jnp.zeros((self.api.cfg.n_layers, 1, self.S,
                       self.api.cfg.n_kv_heads, self.api.cfg.head_dim),
                      self.cache["k"].dtype)
        k = jax.lax.dynamic_update_slice(k, cache["k"], (0, 0, 0, 0, 0))
        v = jnp.zeros_like(k)
        v = jax.lax.dynamic_update_slice(v, cache["v"], (0, 0, 0, 0, 0))
        self.cache["k"] = jax.lax.dynamic_update_slice(
            self.cache["k"], k, (0, slot, 0, 0, 0))
        self.cache["v"] = jax.lax.dynamic_update_slice(
            self.cache["v"], v, (0, slot, 0, 0, 0))
        first = int(jnp.argmax(logits[:, -1, :], axis=-1)[0])
        req.out.append(first)
        req.slot = slot
        self.active[slot] = True
        self.slot_req[slot] = req
        self.pos = self.pos.at[slot].set(S_p)
        self.tokens = self.tokens.at[slot, 0].set(first)
        return True

    def decode_step(self):
        """One gang-schedulable decode quantum over all active slots."""
        if not self.active.any():
            return
        logits, self.cache = self._decode(self.params, self.cache,
                                          self.tokens, self.pos)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        nxt_host = np.asarray(nxt)
        self.decode_steps += 1
        for slot in range(self.B):
            if not self.active[slot]:
                continue
            req = self.slot_req[slot]
            req.out.append(int(nxt_host[slot]))
            if len(req.out) >= req.max_new or \
                    int(self.pos[slot]) + 2 >= self.S:
                req.done = True
                self.active[slot] = False
                self.slot_req[slot] = None
        self.pos = self.pos + 1
        self.tokens = nxt[:, None]

    def run_until_done(self, reqs: List[Request], max_steps: int = 10_000):
        pending = list(reqs)
        done: List[Request] = []
        steps = 0
        while (pending or self.active.any()) and steps < max_steps:
            while pending and self.add_request(pending[0]):
                pending.pop(0)
            self.decode_step()
            steps += 1
            done.extend([r for r in reqs if r.done and r not in done])
        return reqs
