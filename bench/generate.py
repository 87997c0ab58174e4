"""Inputs from ``--seed``: random streams and camera frames.

Every seed gets the same work: a traffic mix (``traffic/<mix>.json``)
fixes the release period and the co-runner, and only the frames' pixels
and the weights change with the seed.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """numpy generator for one named use of ``seed`` (any size of int)."""
    return np.random.default_rng([stream, seed])


def jax_key(seed: int, stream: int):
    """A JAX key for one named use of ``seed``. JAX keeps only the low 64
    bits of a seed, so seeds are first hashed to 32 bits here."""
    import jax
    word = np.random.SeedSequence([stream, seed]).generate_state(1, np.uint32)
    return jax.random.key(int(word[0]))


def frames(n: int, hw, channels: int, seed: int) -> np.ndarray:
    """``n`` camera frames of uint8 pixels, as a camera delivers them."""
    return rng(seed, 13).integers(0, 256, size=(n, *hw, channels),
                                  dtype=np.uint8)
