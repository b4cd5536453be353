"""Epoch-advance kernel: decayed card-access-rate EMA (always-on profiling).

This kernel folds the card table into the per-page CAR EMA at an epoch
boundary.  Each grid step reduces a tile of pages' card bits to the
epoch-window CAR (popcount / allocated cards) and blends it into the
running EMA:

    ema' = decay * ema + (1 - decay) * popcount(cat) / max(alloc, 1)

The epoch governor (``plane.advance_epoch``) recomputes every allocated
page's PSF from this decayed CAR — path selection adapts online instead of
waiting for a page-out — and the caller clears the card table to open the
next epoch window.

Layout: pages run along the lanes.  The card table arrives transposed,
``[P, V]``, and the per-page vectors as ``[1, V]`` rows, so every block is
lane-dense; a ``[V, 1]`` column would pad each page to a 128-lane tile.
``V`` is padded to a multiple of the page tile (a multiple of 128).

Shapes: cat_t [P, V] int32 (0/1 card bits), car_ema [1, V] float32,
        alloc [1, V] int32 -> new_ema [1, V] float32
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

MAX_PAGE_TILE = 8192     # pages per grid step (lanes)


def _kernel(cat_ref, ema_ref, alloc_ref, out_ref, *, decay: float):
    cnt = jnp.sum(cat_ref[...].astype(jnp.float32), axis=0, keepdims=True)
    denom = jnp.maximum(alloc_ref[...], 1).astype(jnp.float32)
    car = cnt / denom
    out_ref[...] = jnp.float32(decay) * ema_ref[...] + \
        jnp.float32(1.0 - decay) * car


@functools.partial(jax.jit, static_argnames=("decay", "interpret"))
def cat_decay(cat_t: jnp.ndarray, car_ema: jnp.ndarray, alloc: jnp.ndarray,
              *, decay: float, interpret: bool = False) -> jnp.ndarray:
    """cat_t [P, V] int32, car_ema [1, V] f32, alloc [1, V] i32 -> [1, V]."""
    P, V = cat_t.shape
    tile = min(MAX_PAGE_TILE, -(-V // 128) * 128)
    pad = -V % tile
    if pad:
        cat_t = jnp.pad(cat_t, ((0, 0), (0, pad)))
        car_ema = jnp.pad(car_ema, ((0, 0), (0, pad)))
        alloc = jnp.pad(alloc, ((0, 0), (0, pad)))
    row = pl.BlockSpec((1, tile), lambda v: (0, v))
    out = pl.pallas_call(
        functools.partial(_kernel, decay=decay),
        grid=((V + pad) // tile,),
        in_specs=[pl.BlockSpec((P, tile), lambda v: (0, v)), row, row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((1, V + pad), jnp.float32),
        interpret=interpret,
    )(cat_t, car_ema, alloc)
    return out[:, :V]
