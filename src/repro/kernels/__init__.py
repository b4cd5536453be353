"""Pallas TPU kernels for the plane's compute hot spots.

Per-kernel modules hold the ``pl.pallas_call`` + BlockSpec implementations;
``ref.py`` holds the pure-jnp oracles; ``ops.py`` is the jitted dispatch
surface used by the rest of the framework.

Kernels:
  * ``gather_objects``  — far-tier ingress: row gather (runtime path,
    evacuator page assembly) and page gather (paging path)
  * ``cat_decay``       — epoch-advance CAR EMA (the governor's profile)
  * ``paged_attention`` — decode attention through the page table
  * ``cat_update``      — card-table update + CAR popcount (no caller)
  * ``topk_pages``      — offload-space page scoring for sparse attention
"""
from . import ops, ref
from .ops import (cat_update, compact_pages, gather_rows, page_scores,
                  paged_attention)

__all__ = ["ops", "ref", "cat_update", "compact_pages", "gather_rows",
           "page_scores", "paged_attention"]
