"""Random weights from a seed, identical however they are batched.

Every element is a pure function of (seed, leaf id, layer, element
index): an integer hash (murmur3's 32-bit finalizer) turned into a
uniform number in [-1, 1).  Integer ops and one rounding multiply are
exact on every backend, so the serving parameters made in one jitted call
for all layers and the reference's weights made one layer at a time
hold the same values bit for bit, without the reference ever reading
what the program holds.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_M1, _M2, _GOLD = 0x85EBCA6B, 0xC2B2AE35, 0x9E3779B9


def seed_words(seed: int) -> np.ndarray:
    """The seed as two uint32 words (traced, so no seed recompiles)."""
    seed = int(seed)
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def _fmix(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_M1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_M2)
    return h ^ (h >> 16)


def _key(words, leaf: int, layer):
    k = _fmix(jnp.uint32(leaf) * jnp.uint32(_GOLD) + jnp.asarray(layer, jnp.uint32))
    k = _fmix(k ^ words[1])
    return _fmix(k ^ words[0] ^ jnp.uint32(0x5BD1E995))


def unit(words, leaf: int, layer, shape) -> jnp.ndarray:
    """f32 in [0, 1), multiples of 2**-24, for one layer's leaf."""
    n = math.prod(shape)
    idx = jax.lax.iota(jnp.uint32, n).reshape(shape)
    h = _fmix((idx * jnp.uint32(_GOLD)) ^ _key(words, leaf, layer))
    return (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def uniform(words, leaf: int, layer, shape, std: float) -> jnp.ndarray:
    """Uniform values of standard deviation ``std`` (f32)."""
    a = jnp.float32(std * math.sqrt(3.0))
    return (unit(words, leaf, layer, shape) * 2.0 - 1.0) * a


def stacked(fn, n_layers: int):
    """``fn(layer) -> leaf`` for every layer, stacked on axis 0."""
    return jax.vmap(fn)(jnp.arange(n_layers, dtype=jnp.uint32))
