"""Jitted production wrappers for the Pallas kernels.

Dispatch policy (``impl``):
  * ``"auto"``      — Pallas on TPU backends, jnp reference otherwise.  The
    reference path is what the CPU-backend multi-pod dry-run lowers (Pallas
    TPU custom calls cannot lower on CPU); on a real pod the Pallas path is
    taken.  Both compute identical values (asserted by the kernel tests).
  * ``"pallas"``    — force the compiled Pallas kernel.
  * ``"interpret"`` — Pallas kernel body executed in interpret mode
    (kernel-correctness validation on CPU).
  * ``"ref"``       — force the pure-jnp oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .cat_decay import cat_decay as _cat_decay_pallas
from .cat_update import cat_update as _cat_pallas
from .gather_objects import gather_pages as _gather_pages_pallas
from .gather_objects import gather_rows as _gather_pallas
from .paged_attention import paged_attention as _paged_attn_pallas
from .topk_pages import page_scores as _scores_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(impl: str) -> str:
    """The implementation ``impl`` runs as: ``"auto"`` resolves to
    ``"pallas"`` on TPU and ``"ref"`` elsewhere."""
    if impl == "auto":
        return "pallas" if _on_tpu() else "ref"
    return impl


def gather_rows(pool, idx, *, impl="auto", masked=True):
    """pool [N, D], idx [R] -> [R, D].  With ``masked`` (default) negative
    indices yield zero rows; ``masked=False`` skips the zero-fill pass (the
    jnp path's extra select over the result) when the caller never consumes
    rows at negative indices — e.g. when they are dropped by a downstream
    masked scatter."""
    m = resolve_impl(impl)
    if m == "ref":
        if not masked:
            return pool[jnp.maximum(idx, 0)]
        return ref.gather_rows_ref(pool, idx)
    return _gather_pallas(pool, idx, interpret=(m == "interpret"))


def gather_pages(slab, page_ids, perm=None, *, impl="auto", masked=True):
    """Multi-head page assembly in ONE batched page gather.

    slab [KVH, S, P, Dh]; page_ids [N] int32 (-1 = masked, yields zero
    pages); optional perm [N, P] row permutation applied to each fetched
    page (the runtime path's hot-row packing) -> [KVH, N, P, Dh].

    The pool is viewed page-granularly ([KVH*S, P, Dh]) so each fetched
    page is ONE kernel block — one DMA per page per head, all heads in a
    single kernel launch.  The packing permutation runs locally on the
    fetched tile (egress from the far tier is always page-granular;
    packing is a local-space relayout)."""
    KVH, S, P, Dh = slab.shape
    N = page_ids.shape[0]
    base = jnp.arange(KVH, dtype=jnp.int32)[:, None] * S
    idx = jnp.where(page_ids[None] >= 0, base + page_ids[None], -1).reshape(-1)
    m = resolve_impl(impl)
    if m == "ref":
        pages = gather_rows(slab.reshape(KVH * S, P * Dh), idx, impl=m,
                            masked=masked)
    else:
        pages = _gather_pages_pallas(slab.reshape(KVH * S, P, Dh), idx,
                                     interpret=(m == "interpret"))
    pages = pages.reshape(KVH, N, P, Dh)
    if perm is not None:
        pages = jnp.take_along_axis(pages, perm[None, :, :, None], axis=2)
    return pages


def cat_update(cat_bits, vaddrs, *, page_objs: int, impl="auto"):
    """Returns (bits, car[V] float32)."""
    m = resolve_impl(impl)
    if m == "ref":
        return ref.cat_update_ref(cat_bits, vaddrs, page_objs)
    bits, counts = _cat_pallas(cat_bits, vaddrs, page_objs=page_objs,
                               interpret=(m == "interpret"))
    return bits, counts[:, 0].astype(jnp.float32) / jnp.float32(page_objs)


def cat_decay(cat, car_ema, alloc, *, decay: float, impl="auto"):
    """Epoch-advance CAR EMA.  cat [V, P] bool, car_ema [V] f32,
    alloc [V] i32 -> new_ema [V] f32 (see kernels.cat_decay)."""
    m = resolve_impl(impl)
    cat_i = cat.astype(jnp.int32)
    if m == "ref":
        return ref.cat_decay_ref(cat_i, car_ema, alloc, decay)
    out = _cat_decay_pallas(cat_i.T, car_ema[None], alloc[None],
                            decay=decay, interpret=(m == "interpret"))
    return out[0]


def paged_attention(q, k_pages, v_pages, page_table, page_lens, *, impl="auto"):
    """q [B, H, Dh]; k/v_pages [KVH, F, P, Dh]; page_table [B, NP];
    page_lens [B, NP] (valid rows per column).

    Returns (out [B, H, Dh], row_used [B, NP, P] bool) — ``row_used`` is the
    card-profiling signal: rows whose attention weight exceeded the
    within-page mean."""
    m = resolve_impl(impl)
    if m == "ref":
        return ref.paged_attention_ref(q, k_pages, v_pages, page_table,
                                       page_lens)
    B, H, Dh = q.shape
    KVH = k_pages.shape[0]
    G = H // KVH
    out, used = _paged_attn_pallas(q.reshape(B, KVH, G, Dh), k_pages, v_pages,
                                   page_table.reshape(-1),
                                   page_lens.reshape(-1),
                                   interpret=(m == "interpret"))
    return out.reshape(B, H, Dh), used.astype(bool).any(axis=1)


def lengths_to_page_lens(lengths, num_pages: int, page_tokens: int):
    """Dense layout helper: [B] total lengths -> [B, NP] per-page rows."""
    starts = jnp.arange(num_pages) * page_tokens
    return jnp.clip(lengths[:, None] - starts[None, :], 0, page_tokens
                    ).astype(jnp.int32)


def compact_pages(pool, plan, *, page_objs: int, impl="auto"):
    """pool [N, D], plan [M*P] flat row ids -> assembled pages [M, P, D].

    The evacuator's page assembly: each destination slot gathers its
    source row (-1 = zero-filled slot), so it is one row gather."""
    M = plan.shape[0] // page_objs
    return gather_rows(pool, plan, impl=impl).reshape(M, page_objs,
                                                      pool.shape[-1])


def page_scores(q, kmax, kmin, *, impl="auto"):
    """q [B, H, Dh] -> scores [B, KVH, NP] float32."""
    m = resolve_impl(impl)
    if m == "ref":
        return ref.page_scores_ref(q, kmax, kmin)
    B, H, Dh = q.shape
    KVH, NP, _ = kmax.shape
    G = H // KVH
    blk = 128 if NP % 128 == 0 else NP
    return _scores_pallas(q.reshape(B, KVH, G, Dh), kmax, kmin,
                          block_pages=blk, interpret=(m == "interpret"))


# --------------------------------------------------------------------------
# packed-payload layouts for the sharded exchange (repro.core.shardplane)
# --------------------------------------------------------------------------
# The exchange used to move ids, duplicate counts and served flags as
# separate collectives; these helpers fuse the side channels into ONE
# payload per direction so each round pays exactly two all_to_all hops.
# They are axis-agnostic (pure stack/concat on the trailing axes), so the
# same layout serves the per-shard [S, B] buffers inside shard_map and the
# stacked [S, S, B] buffers of the single-device oracle — fusing then
# splitting is bitwise lossless either way.

def fuse_ids_counts(ids, cnt):
    """ids [..., B] int32 + cnt [..., B] int32 -> [..., 2, B] payload."""
    return jnp.stack([ids, cnt], axis=-2)


def split_ids_counts(payload):
    """Inverse of :func:`fuse_ids_counts`."""
    return payload[..., 0, :], payload[..., 1, :]


def fuse_rows_flags(rows, flags):
    """rows [..., B, D] + flags [..., B] bool -> [..., B, D+1] payload.

    The bool rides as an extra 0/1 column in the row dtype — exact in
    every float format down to bf16, so the round-trip is lossless."""
    return jnp.concatenate(
        [rows, flags[..., None].astype(rows.dtype)], axis=-1)


def split_rows_flags(payload):
    """Inverse of :func:`fuse_rows_flags`."""
    return payload[..., :-1], payload[..., -1] > 0
