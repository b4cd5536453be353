"""Record values, built on the device from ``--seed``.

Record ``id``'s field word ``col`` is ``value(seed, id, col)``: a 32-bit
integer hash of ``(seed, id * width + col)`` whose top 24 bits become a
float32 in ``[0, 1)``, so every value uses the whole float32 significand
and any rounding to a narrower type changes it.  ``reference.py`` computes
the same values with numpy; the two are kept apart so that a fault in
either shows as a mismatch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _mix(x):
    # lowbias32 (C. Wellons): a bijective 32-bit integer hash
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def seed_words(seed: int) -> jnp.ndarray:
    """The seed as two uint32 words, passed as data: one program serves
    every seed."""
    return jnp.asarray([int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF],
                       jnp.uint32)


def _values(words, ids, width: int):
    k0 = _mix(words[0])
    k1 = _mix(words[1] + jnp.uint32(0x9E3779B9))
    x = (ids.astype(jnp.uint32)[:, None] * jnp.uint32(width)
         + jnp.arange(width, dtype=jnp.uint32)[None, :])
    h = _mix(_mix(x ^ k0) ^ k1)
    return (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


@functools.lru_cache(maxsize=None)
def _program(num: int, width: int, sharding):
    return jax.jit(lambda w: _values(w, jnp.arange(num, dtype=jnp.uint32),
                                     width),
                   out_shardings=sharding)


def build(seed: int, num: int, width: int, sharding=None) -> jax.Array:
    """All ``num`` records ``[num, width]`` f32 in one jitted call on the
    device (laid out by ``sharding`` when given)."""
    return _program(num, width, sharding)(seed_words(seed))
