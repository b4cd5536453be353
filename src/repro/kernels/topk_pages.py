"""Sparse-attention page scoring: the offload-space computation.

Scores every KV page against the current queries using Quest-style
min/max key summaries WITHOUT fetching the pages themselves — the summaries
are tiny and stay local while the page data may be far-resident.  The
plane's sparse path then object-fetches only the top-k pages' rows.

score[b, h, n] = max_g sum_d max(q[b,h,g,d] * kmax[h,n,d],
                                 q[b,h,g,d] * kmin[h,n,d])

Shapes: q [B, KVH, G, Dh], kmax/kmin [KVH, NP, Dh] -> scores [B, KVH, NP]
(NP must be a multiple of the page block, default 128; the block is a
multiple of 128 or all of NP).  The kernel writes ``[B, KVH, 1, NP]`` so
each output block's trailing dims are ``(1, NPB)``, legal for Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, kmax_ref, kmin_ref, out_ref):
    q = q_ref[0, 0].astype(jnp.float32)          # [G, Dh]
    kmax = kmax_ref[0].astype(jnp.float32)       # [NPB, Dh]
    kmin = kmin_ref[0].astype(jnp.float32)       # [NPB, Dh]
    # [G, NPB, Dh] elementwise upper bound, reduce over Dh then G
    hi = q[:, None, :] * kmax[None, :, :]
    lo = q[:, None, :] * kmin[None, :, :]
    ub = jnp.maximum(hi, lo).sum(axis=-1)        # [G, NPB]
    out_ref[...] = jnp.max(ub, axis=0, keepdims=True)   # [1, NPB]


@functools.partial(jax.jit, static_argnames=("block_pages", "interpret"))
def page_scores(q: jnp.ndarray, kmax: jnp.ndarray, kmin: jnp.ndarray, *,
                block_pages: int = 128, interpret: bool = False) -> jnp.ndarray:
    B, KVH, G, Dh = q.shape
    _, NP, _ = kmax.shape
    NPB = min(block_pages, NP)
    assert NP % NPB == 0, (NP, NPB)

    grid = (B, KVH, NP // NPB)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, Dh), lambda b, h, n: (b, h, 0, 0)),
            pl.BlockSpec((1, NPB, Dh), lambda b, h, n: (h, n, 0)),
            pl.BlockSpec((1, NPB, Dh), lambda b, h, n: (h, n, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, 1, NPB),
                               lambda b, h, n: (b, h, 0, n)),
        out_shape=jax.ShapeDtypeStruct((B, KVH, 1, NP), jnp.float32),
        interpret=interpret,
    )(q, kmax, kmin).reshape(B, KVH, NP)
