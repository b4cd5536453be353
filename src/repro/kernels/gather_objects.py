"""Far-tier gathers: fetch R scattered rows, or R whole pages, from a pool.

This is the batched data movement of both ingress paths.  On TPU the
indices are *scalar-prefetched* so each block's HBM->VMEM DMA is issued
ahead of the copy — the TPU-native replacement for AIFM's RDMA reads of
individual objects (rows) and the kernel's page faults (pages).

Block layouts (Mosaic needs the last two block dims divisible by the
(sublane, lane) tiling or equal to the array's own dims):

* ``gather_pages``: pool ``[N, P, D]``, one ``(P, D)`` page per grid
  step — the block is the array's whole trailing extent, so any ``P`` and
  ``D`` are legal and the pool is read in place.
* ``gather_rows``: pool ``[N, D]`` is viewed as ``[N/g, g, D]`` tile
  groups (``g`` = the dtype's sublane tile, 8 rows of f32, when it divides
  ``N``); each step DMAs the group holding the row and copies the row out.
  A ``(1, D)`` block is refused, and re-laying the pool out as
  ``[N, 1, D]`` would copy the whole pool on every call, so the kernel
  reads ``g`` rows to serve one.

Masked indices (-1) yield zero rows / pages.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _row_kernel(idx_ref, grp_ref, out_ref, *, group: int):
    # grp_ref: [group, D] tile group holding the row; out_ref: [1, D].
    ix = idx_ref[pl.program_id(0)]
    row = grp_ref[pl.ds(jnp.maximum(ix, 0) % group, 1), :]
    out_ref[...] = jnp.where(ix >= 0, row, jnp.zeros_like(row))


def _page_kernel(idx_ref, page_ref, out_ref):
    # page_ref / out_ref: [P, D] page blocks.
    ix = idx_ref[pl.program_id(0)]
    out_ref[...] = jnp.where(ix >= 0, page_ref[...],
                             jnp.zeros_like(page_ref))


def _gather(kernel, pool, idx, block, index_map, out_rows, interpret):
    """Shared grid: one step per index over a 3-D pool, [R, *block] out."""
    R = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R,),
        in_specs=[pl.BlockSpec((None,) + block, index_map)],
        out_specs=pl.BlockSpec((None, out_rows, block[1]),
                               lambda i, idx_ref: (i, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, out_rows, block[1]), pool.dtype),
        interpret=interpret,
    )(idx, pool)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(pool: jnp.ndarray, idx: jnp.ndarray, *,
                interpret: bool = False) -> jnp.ndarray:
    """Pallas row gather.  pool [N, D], idx [R] -> [R, D]."""
    N, D = pool.shape
    group = math.gcd(N, max(1, 32 // pool.dtype.itemsize))
    out = _gather(
        functools.partial(_row_kernel, group=group),
        pool.reshape(N // group, group, D), idx, (group, D),
        lambda i, idx_ref: (jnp.maximum(idx_ref[i], 0) // group, 0, 0),
        1, interpret)
    return out.reshape(idx.shape[0], D)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_pages(pool: jnp.ndarray, idx: jnp.ndarray, *,
                 interpret: bool = False) -> jnp.ndarray:
    """Pallas page gather.  pool [N, P, D], idx [R] -> [R, P, D]."""
    _, P, D = pool.shape
    return _gather(_page_kernel, pool, idx, (P, D),
                   lambda i, idx_ref: (jnp.maximum(idx_ref[i], 0), 0, 0),
                   P, interpret)
