"""Paged decode attention: the paging-path consumer of the tiered KV cache.

One new query token per sequence attends over a KV cache stored as *pages*
(frames) indirected through the plane's page table — the TPU-native analogue
of reading through the kernel's paging system.  Page-table entries are
scalar-prefetched so each logical page's HBM->VMEM DMA is issued ahead of
the compute (streamed, double-buffered by the Pallas pipeline).

Shapes:
    q           [B, KVH, G, Dh]   (H = KVH * G query heads, GQA)
    k_pages     [KVH, F, P, Dh]   frame pool
    v_pages     [KVH, F, P, Dh]
    page_table  [B * NP] int32    frame id per (seq, logical page), -1 unused
    page_lens   [B * NP] int32    valid rows per (seq, logical page)
    out         [B, KVH, G, Dh]
    used        [B, KVH, NP, 1, P] int8   card-profiling bits per page row
                (the unit dim keeps each page's ``(1, P)`` block equal to
                the array's trailing dims, as Mosaic requires)

Online-softmax accumulation in f32 VMEM scratch; grid (B, KVH, NP) with the
page dimension innermost so scratch carries across pages of one (seq, head).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(pt_ref, plen_ref, q_ref, k_ref, v_ref, out_ref, used_ref,
            m_ref, l_ref, acc_ref, *, num_pages: int, page_objs: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = plen_ref[b * num_pages + j]
    frame = pt_ref[b * num_pages + j]
    valid_page = jnp.logical_and(frame >= 0, rows > 0)
    used_ref[...] = jnp.zeros(used_ref.shape, used_ref.dtype)

    @pl.when(valid_page)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32)          # [G, Dh]
        k = k_ref[0, 0].astype(jnp.float32)          # [P, Dh]
        v = v_ref[0, 0].astype(jnp.float32)          # [P, Dh]
        dh = q.shape[-1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s *= jax.lax.rsqrt(jnp.float32(dh))          # [G, P]
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(row < rows, s, NEG_INF)

        m_prev = m_ref[...]                          # [G, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                       # [G, P]
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        # card profiling: row used if its (unnormalized) weight exceeds the
        # within-page mean for any query of the group
        mass = jnp.sum(p, axis=1, keepdims=True)     # [G, 1]
        used = jnp.logical_and(p * page_objs > mass, row < rows)
        used_ref[...] = jnp.any(used, axis=0).reshape(
            used_ref.shape).astype(used_ref.dtype)

    @pl.when(j == num_pages - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        out_ref[0, 0] = (acc_ref[...] / l).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                    page_table: jnp.ndarray, page_lens: jnp.ndarray, *,
                    interpret: bool = False) -> jnp.ndarray:
    B, KVH, G, Dh = q.shape
    _, F, P, _ = k_pages.shape
    NP = page_table.shape[0] // B

    def _clamped(i, pt_ref):
        return jnp.maximum(pt_ref[i], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KVH, NP),
        in_specs=[
            pl.BlockSpec((1, 1, G, Dh),
                         lambda b, h, j, pt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, P, Dh),
                         lambda b, h, j, pt, ln: (h, _clamped(b * NP + j, pt), 0, 0)),
            pl.BlockSpec((1, 1, P, Dh),
                         lambda b, h, j, pt, ln: (h, _clamped(b * NP + j, pt), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, Dh), lambda b, h, j, pt, ln: (b, h, 0, 0)),
            pl.BlockSpec((None, None, None, 1, P),
                         lambda b, h, j, pt, ln: (b, h, j, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, Dh), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, num_pages=NP, page_objs=P)
    out, used = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KVH, G, Dh), q.dtype),
                   jax.ShapeDtypeStruct((B, KVH, NP, 1, P), jnp.int8)],
        interpret=interpret,
    )(page_table, page_lens, q, k_pages, v_pages)
    return out, used.reshape(B, KVH, NP, P)
