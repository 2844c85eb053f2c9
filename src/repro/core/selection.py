"""Two-stage hierarchical selection pipeline (ROADMAP item 2).

This module unifies the selection machinery that used to live in three
places — dim-block top-k in ``core/aqua.py``, backend dispatch and chunk
tile masks in ``core/attention.py``, and per-kernel index plumbing — into
one pipeline behind :class:`repro.configs.base.SparsitySpec`, producing a
per-step :class:`SelectionPlan`:

  * **Stage 1 — token sparsity (page-granular):** rank a lane's mapped
    pages by the H2O accumulated attention mass the paged pool already
    maintains (``PagedAttnCache.acc_pool``) and keep only the top
    ``kept_pages`` as *participants*; the trailing ``pin_recent_pages``
    pages (probe token, local window) are always kept. This is the
    HyperAttention composition — a coarse token-level stage in front of a
    finer approximation — but reusing our own statistics instead of LSH.
  * **Stage 2 — dim sparsity:** AQUA's per-query |q̂| dim-block top-k
    (``core.aqua.topk_block_indices``), unchanged, applied only within
    participating pages.

The participation table is composed into each lane's page list, which
the paged decode kernel scalar-prefetches like its quant scales
(``kernels/aqua_decode.py``), so non-participating pages cost zero HBM
bytes: decode bandwidth scales with ``kept_pages``, not context length.
``page_keep_ratio=1.0`` resolves to the identity participation table —
the kernel walks the same pages in the same order and is bit-identical
to the plain paged path.

Ranking semantics (shared by the jit path, the numpy ``--verify``
oracle, and the property tests):

  * page mass = per-lane sum of the page's ``acc_pool`` scores, gathered
    through the lane's own page table — shared/CoW physical pages score
    *per lane*, not per pool;
  * the trailing ``pin_recent_pages`` mapped pages rank ``+inf``
    (recency pin — never dropped);
  * logical pages beyond the lane's token count rank ``-inf`` (they hold
    no attendable tokens; keeping them last makes the table
    deterministic — kernel validity masking drops them anyway);
  * ties resolve to the lowest page index (``lax.top_k`` semantics), so
    a zero-statistic cache degrades to attention-sink (earliest pages)
    plus the pinned recent tail;
  * the participating set is sorted ascending, so a full keep ratio is
    the identity map.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aqua as aqua_lib


@jax.tree_util.register_dataclass
@dataclass
class SelectionPlan:
    """One decode step's resolved two-stage selection.

    block_idx: (B, H, NB_sel) int32 — stage-2 dim-block indices (sorted
       ascending; ``core.aqua.topk_block_indices``).
    pages: (B, KP) int32 — stage-1 participating *logical* page indices
       per lane (sorted ascending), or None when every page participates
       (no token sparsity). Entries are always valid logical indices in
       ``[0, pages_per_lane)``; empty/unmapped pages that pad the set are
       masked by the kernels' position validity test.
    """

    block_idx: jax.Array
    pages: Optional[jax.Array] = None


def page_scores(acc_pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """Per-lane page mass: (P, KV, ps) pool × (B, NP) table -> (B, NP).

    Gathered through the lane's table so shared (CoW/prefix) physical
    pages contribute to every lane that maps them — ranking is per-lane.
    Unmapped entries (-1) score 0 instead of borrowing page 0's mass.
    """
    score = acc_pool[jnp.maximum(page_table, 0)].sum(axis=(2, 3))
    return jnp.where(page_table >= 0, score, 0.0)


def participating_pages(acc_pool: jax.Array, page_table: jax.Array,
                        count: jax.Array, *, page_size: int,
                        kept_pages: int,
                        pin_recent_pages: int) -> jax.Array:
    """Stage-1 selection: (B, kept_pages) int32 logical page indices,
    sorted ascending (see the module docstring for ranking semantics).
    ``count`` (B,) is the lane's token count at read time — the page
    holding position ``count - 1`` anchors the recency pin.
    """
    b, npl = page_table.shape
    score = page_scores(acc_pool, page_table)                  # (B, NP)
    pidx = jnp.arange(npl, dtype=jnp.int32)[None, :]
    tail = jnp.maximum((count[:, None] - 1) // page_size, 0)   # (B, 1)
    pinned = (pidx > tail - pin_recent_pages) & (pidx <= tail)
    score = jnp.where(pinned, jnp.inf, score)
    score = jnp.where(pidx > tail, -jnp.inf, score)
    _, top = jax.lax.top_k(score, kept_pages)
    return jnp.sort(top, axis=-1).astype(jnp.int32)


def reference_participating_pages(acc_pool, page_table, count, *,
                                  page_size: int, kept_pages: int,
                                  pin_recent_pages: int) -> np.ndarray:
    """Numpy twin of :func:`participating_pages` — the ``--verify``
    page-ranking oracle and the property-test reference. Identical
    ranking, pin, tie (stable lowest-index-first) and sort semantics,
    computed host-side in float32 like the jit path."""
    acc = np.asarray(acc_pool)
    table = np.asarray(page_table)
    cnt = np.asarray(count)
    b, npl = table.shape
    out = np.zeros((b, kept_pages), np.int32)
    pidx = np.arange(npl)
    for i in range(b):
        score = acc[np.maximum(table[i], 0)].sum(
            axis=(1, 2), dtype=np.float32)
        score[table[i] < 0] = 0.0
        tail = max((int(cnt[i]) - 1) // page_size, 0)
        score[(pidx > tail - pin_recent_pages) & (pidx <= tail)] = np.inf
        score[pidx > tail] = -np.inf
        top = np.argsort(-score, kind="stable")[:kept_pages]
        out[i] = np.sort(top)
    return out


def build_decode_plan(q_hat: jax.Array, cache, *, topk_dims: int,
                      block_dims: int,
                      kept_pages: Optional[int] = None,
                      pin_recent_pages: int = 2) -> SelectionPlan:
    """Resolve one decode step's :class:`SelectionPlan`.

    q_hat: (B, H, Dk) projected (unmasked) queries, head-flattened as the
    decode kernels consume them. ``cache`` is a
    :class:`repro.core.kvcache.PagedAttnCache`. ``kept_pages`` None (or
    the full page count) disables stage 1 — ``plan.pages`` is None and
    the kernels take their existing non-hierarchical path.
    """
    block_idx = aqua_lib.topk_block_indices(q_hat, topk_dims, block_dims)
    pages = None
    if kept_pages is not None and kept_pages < cache.pages_per_lane:
        pages = participating_pages(
            cache.acc_pool, cache.page_table, cache.count,
            page_size=cache.page_size, kept_pages=kept_pages,
            pin_recent_pages=pin_recent_pages)
    return SelectionPlan(block_idx=block_idx, pages=pages)


def participation_slot_mask(pages: jax.Array, *, page_size: int,
                            num_slots: int) -> jax.Array:
    """(B, KP) participating pages -> (B, S_log) bool slot mask — the
    masked-dense reference's view of stage 1 (slot attendable iff its
    logical page participates). The reference path composes this with
    the usual position validity mask so it attends exactly the token set
    the hierarchical kernel streams."""
    npl = num_slots // page_size
    hit = (jnp.arange(npl, dtype=jnp.int32)[None, :, None]
           == pages[:, None, :]).any(-1)                       # (B, NP)
    return jnp.repeat(hit, page_size, axis=1)


def chunk_participating_tiles(scores: jax.Array, *, nqc: int, q_blk: int,
                              k_blk: int, kept_tiles: int,
                              pin_tiles: int = 1,
                              q_offset: int = 0) -> jax.Array:
    """Q-tile-granular stage-1 analogue for the chunked prefill kernel.

    ``scores`` (B, NKC): per-k-tile mass (e.g. page mass from earlier
    chunks aggregated to kernel tiles; zeros degrade to sink + diagonal).
    For each q-tile the ``pin_tiles`` k-tiles at the causal diagonal are
    pinned (the tile attending itself is always exact) and tiles strictly
    beyond the diagonal rank ``-inf`` (the kernel's causal skip ignores
    them regardless). Returns (B, NQC, kept_tiles) int32, sorted
    ascending per q-tile.
    """
    b, nkc = scores.shape
    diag = (q_offset + (jnp.arange(nqc) + 1) * q_blk - 1) // k_blk
    tidx = jnp.arange(nkc, dtype=jnp.int32)[None, None, :]
    d = diag[None, :, None]
    s = jnp.broadcast_to(scores[:, None, :].astype(jnp.float32),
                         (b, nqc, nkc))
    s = jnp.where((tidx > d - pin_tiles) & (tidx <= d), jnp.inf, s)
    s = jnp.where(tidx > d, -jnp.inf, s)
    _, top = jax.lax.top_k(s, kept_tiles)
    return jnp.sort(top, axis=-1).astype(jnp.int32)
