"""Decode-time caches.

One unified slot-based cache covers every policy in the framework:

  * standard full cache        (slots = max_seq, slot s holds position s)
  * sliding / local window     (slots = window, ring buffer)
  * H2O heavy-hitter budget    (slots = budget, victim = argmin acc score)
  * AQUA projected cache       (keys stored projected, dim-major [D, S],
                                optionally statically sliced — AQUA-Memory)

Slots carry an explicit ``positions`` array so masking, RoPE and recency
protection are uniform across policies. Everything is static-shaped and
jit/pjit friendly.

Block-paged variant (:class:`PagedAttnCache`): the same *logical* slot
space per lane, but physical storage lives in a global page pool shared
by all lanes — per-lane page tables map logical page ``slot // page_size``
to a physical pool page. HBM footprint scales with the pool size (actual
occupancy) instead of ``lanes × max_seq``, read-only pages can be mapped
into several lanes at once (prefix sharing, refcounted host-side by
``repro.serving.scheduler.PagePool``), and H2O eviction turns
page-granular: the accumulated-score victim frees a *whole page*. Because
the logical slot space is unchanged, the full-cache and sliding-window
policies are slot-for-slot identical to the contiguous cache (paged
decode is token-identical at greedy); only the H2O policy deliberately
diverges to whole-page victims. All paged operations are static-shaped
and jit-safe: the host allocator only ever writes page-table rows between
steps.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclass
class AttnCache:
    """Per-layer attention cache.

    k: (B, KV, S_slots, Dk)  — keys; *projected and sliced* when AQUA is on.
       Stored seq-major here; the Pallas decode kernel consumes the
       dim-major transpose view (see kernels/aqua_decode.py).
    v: (B, KV, S_slots, Dv)
    positions: (B, S_slots) int32 — token position held by each slot, -1 empty.
    count: (B,) int32 — number of tokens processed so far (= next position).
    acc_score: (B, KV, S_slots) f32 — H2O accumulated attention mass
       (zeros when H2O disabled; kept unconditionally for pytree stability).
    """

    k: jax.Array
    v: jax.Array
    positions: jax.Array
    count: jax.Array
    acc_score: jax.Array

    @property
    def num_slots(self) -> int:
        return self.k.shape[2]


def init_attn_cache(batch: int, num_kv: int, slots: int, dk: int, dv: int,
                    dtype=jnp.bfloat16) -> AttnCache:
    return AttnCache(
        k=jnp.zeros((batch, num_kv, slots, dk), dtype),
        v=jnp.zeros((batch, num_kv, slots, dv), dtype),
        positions=jnp.full((batch, slots), -1, jnp.int32),
        count=jnp.zeros((batch,), jnp.int32),
        acc_score=jnp.zeros((batch, num_kv, slots), jnp.float32),
    )


def cache_slots(max_seq: int, window: Optional[int], h2o_budget: Optional[int]
                ) -> int:
    s = max_seq
    if window is not None:
        s = min(s, window)
    if h2o_budget is not None:
        s = min(s, h2o_budget)
    return max(s, 1)


def select_slot(cache: AttnCache, *, window: Optional[int],
                h2o: bool, recent_len: int) -> jax.Array:
    """Slot index (B,) where the incoming token's K/V should be written.

    Policies: ring buffer (window only), contiguous (full cache), H2O
    heavy-hitter eviction, and the combined window+H2O policy: slots whose
    position has slid out of the attention window are dead weight (the
    valid mask will never admit them again), so they are evicted *first*;
    only when every held slot is still in-window does the accumulated-score
    victim selection kick in.
    """
    b, _, s_slots, _ = cache.k.shape
    count = cache.count  # (B,)
    if window is not None and not h2o:
        # ring buffer
        return count % s_slots
    if not h2o:
        return jnp.minimum(count, s_slots - 1)
    # H2O: free slot while not full, else evict argmin-acc among non-recent.
    cur = count  # position of incoming token
    protected = cache.positions > (cur[:, None] - recent_len)  # (B, S)
    protected |= cache.positions < 0  # can't "evict" empties via score path
    score = cache.acc_score.sum(axis=1)  # (B, S) summed over kv heads
    score = jnp.where(protected, jnp.inf, score)
    if window is not None:
        # combined H2O+window: prefer evicting slots that fell out of the
        # window — they can never be attended again regardless of score.
        stale = (cache.positions >= 0) & \
            (cache.positions <= cur[:, None] - window)
        score = jnp.where(stale & ~protected, -jnp.inf, score)
    victim = jnp.argmin(score, axis=-1).astype(jnp.int32)
    free = jnp.minimum(count, s_slots - 1)
    return jnp.where(count < s_slots, free, victim)


@jax.named_scope("kv.write")
def insert(cache: AttnCache, slot: jax.Array, k_new: jax.Array,
           v_new: jax.Array,
           write_mask: Optional[jax.Array] = None) -> AttnCache:
    """Write one token's (projected/sliced) k, v into ``slot``.

    k_new: (B, KV, Dk); v_new: (B, KV, Dv); slot: (B,).

    ``write_mask`` (B,) bool suppresses the write for masked-off rows:
    their k/v/positions/count are left untouched. The continuous-batching
    engine uses this to freeze inactive lanes while the shared decode step
    runs at static batch shape.
    """
    b = jnp.arange(cache.k.shape[0])
    k = cache.k.at[b, :, slot].set(k_new.astype(cache.k.dtype))
    v = cache.v.at[b, :, slot].set(v_new.astype(cache.v.dtype))
    positions = cache.positions.at[b, slot].set(cache.count)
    acc = cache.acc_score.at[b, :, slot].set(0.0)
    count = cache.count + 1
    if write_mask is not None:
        m = write_mask
        k = jnp.where(m[:, None, None, None], k, cache.k)
        v = jnp.where(m[:, None, None, None], v, cache.v)
        positions = jnp.where(m[:, None], positions, cache.positions)
        acc = jnp.where(m[:, None, None], acc, cache.acc_score)
        count = jnp.where(m, count, cache.count)
    return AttnCache(k=k, v=v, positions=positions, count=count,
                     acc_score=acc)


@jax.named_scope("kv.write")
def lane_write_tail(cache: AttnCache, lane: jax.Array, k_tail: jax.Array,
                    v_tail: jax.Array, positions: jax.Array,
                    start: jax.Array, new_count: jax.Array) -> AttnCache:
    """Write a prefill *chunk*'s K/V into one lane of a contiguous
    full-cache, leaving slots below ``start`` untouched.

    The contiguous counterpart of :func:`paged_write_tail`: k_tail
    (T, KV, Dk) / v_tail (T, KV, Dv) / positions (T,) start at logical
    slot ``start`` (the chunk cursor). Slots at/beyond ``start`` are
    cleared first (positions -1, scores 0) so a recycled lane's previous
    tenant never reads as valid — the first chunk (``start`` 0) therefore
    wipes the whole lane, later chunks only clear ahead of themselves.
    Full-cache slot placement only (slot i holds position i); window
    rings and H2O eviction place slots differently and must keep
    monolithic admission.
    """
    s = cache.num_slots
    t = k_tail.shape[0]
    slot = jnp.arange(s)
    ahead = slot >= start                                # (S,)
    inside = ahead & (slot < start + t)                  # chunk's slots
    src = jnp.clip(slot - start, 0, t - 1)
    # rows are rebuilt with selects and written back whole: the TPU
    # compiler fails on the k/v scatter pair this replaces
    k_row = jnp.where(inside[None, :, None],
                      k_tail[src].transpose(1, 0, 2).astype(cache.k.dtype),
                      cache.k[lane])
    v_row = jnp.where(inside[None, :, None],
                      v_tail[src].transpose(1, 0, 2).astype(cache.v.dtype),
                      cache.v[lane])
    pos_row = jnp.where(inside, positions[src],
                        jnp.where(ahead, -1, cache.positions[lane]))
    acc_row = jnp.where(ahead[None, :], 0.0, cache.acc_score[lane])
    put = lambda a, row: jax.lax.dynamic_update_index_in_dim(a, row, lane, 0)
    return dataclasses.replace(
        cache, k=put(cache.k, k_row), v=put(cache.v, v_row),
        positions=put(cache.positions, pos_row),
        acc_score=put(cache.acc_score, acc_row),
        count=cache.count.at[lane].set(new_count))


def valid_mask(cache: AttnCache, *, window: Optional[int]) -> jax.Array:
    """(B, S_slots) bool — slots attendable by the current token."""
    return valid_mask_from(cache.positions, cache.count, window=window)


def valid_mask_from(positions: jax.Array, count: jax.Array, *,
                    window: Optional[int]) -> jax.Array:
    """``valid_mask`` on bare arrays — the shard_map decode path calls
    this on per-shard cache leaves rather than a full AttnCache."""
    cur = count[:, None] - 1  # position of the token now attending
    m = (positions >= 0) & (positions <= cur)
    if window is not None:
        m &= positions > (cur - window)
    return m


def accumulate_h2o(cache: AttnCache, attn_weights: jax.Array,
                   write_mask: Optional[jax.Array] = None) -> AttnCache:
    """attn_weights: (B, KV, G, S_slots) probabilities for the current step;
    summed over the G query heads of each kv group (H2O statistic).
    ``write_mask`` (B,) freezes masked-off rows (inactive lanes)."""
    upd = attn_weights.astype(jnp.float32).sum(axis=2)
    if write_mask is not None:
        upd = jnp.where(write_mask[:, None, None], upd, 0.0)
    return dataclasses.replace(cache, acc_score=cache.acc_score + upd)


# ---------------------------------------------------------------------------
# Block-paged cache: global page pool + per-lane page tables
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclass
class PagedAttnCache:
    """Per-layer paged attention cache.

    k_pool: (P, KV, page_size, Dk) — global key page pool (projected and
       sliced when AQUA is on; the paged Pallas decode kernel reads these
       seq-major pages whole, see kernels/aqua_decode.py).
    v_pool: (P, KV, page_size, Dv)
    pos_pool: (P, page_size) int32 — token position held by each pool
       slot, -1 empty. Stored per *physical* page: positions of a shared
       (read-only, refcounted) page are identical in every lane that maps
       it, so per-lane copies would be redundant.
    acc_pool: (P, KV, page_size) f32 — H2O accumulated attention mass.
    page_table: (B, pages_per_lane) int32 — physical page backing each
       logical page of the lane, -1 unmapped. Logical slot ``s`` of a lane
       lives at ``(page_table[b, s // page_size], s % page_size)``.
    count: (B,) int32 — tokens processed so far (= next position).

    Quantized pools (``QuantSpec.kv_dtype="int8"``): ``k_pool``/``v_pool``
    hold per-page symmetric-quantized int8 values and the optional scale
    leaves become live —

    k_scale / v_scale: (P, SH) f32 per-page scales beside the page table
       (``real = int * scale``; zero-point 0, scale 0 = unwritten page).
       SH is the scale granularity encoded in the shape: ``num_kv`` for
       per-(page, kv-head) scales, 1 for one shared scale per page.
    k_hot / v_hot: (H, KV, page_size, D) full-precision *hot-resident*
       overlay (mixed precision): the int8 pool stays authoritative and
       always written, residents additionally carry an exact write-through
       copy that readers prefer. ``hot_ids``: (H,) int32 physical page id
       of each resident, -1 empty. Residency follows the H2O accumulated
       scores: grafts promote the freshest page, evicting the
       lowest-score resident; freed/recycled pages are demoted.

    The logical slot space (``pages_per_lane * page_size`` slots) matches
    the contiguous :class:`AttnCache` layout exactly, so every policy's
    slot arithmetic carries over through the indirection.
    """

    k_pool: jax.Array
    v_pool: jax.Array
    pos_pool: jax.Array
    acc_pool: jax.Array
    page_table: jax.Array
    count: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    k_hot: Optional[jax.Array] = None
    v_hot: Optional[jax.Array] = None
    hot_ids: Optional[jax.Array] = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def num_pages(self) -> int:
        return self.k_pool.shape[0]

    @property
    def page_size(self) -> int:
        return self.k_pool.shape[2]

    @property
    def pages_per_lane(self) -> int:
        return self.page_table.shape[1]

    @property
    def num_slots(self) -> int:
        """Logical slots per lane (= contiguous cache's slot count)."""
        return self.pages_per_lane * self.page_size


def paged_pages(slots: int, page_size: int) -> int:
    """Pages per lane for a logical capacity of ``slots``. The logical
    slot space must tile into whole pages so the ring / eviction slot
    arithmetic is identical to the contiguous cache — callers validate
    ``slots % page_size == 0`` (ServingConfig does for serving)."""
    assert slots % page_size == 0, \
        f"cache slots {slots} must be a multiple of page_size {page_size}"
    return slots // page_size


#: int8 symmetric quantization range (zero-point is always 0).
QUANT_MAX = 127.0


def init_paged_cache(batch: int, num_kv: int, num_pages: int,
                     pages_per_lane: int, page_size: int, dk: int, dv: int,
                     dtype=jnp.bfloat16, kv_dtype: Optional[str] = None,
                     scale_granularity: str = "page_head",
                     hot_pages: int = 0) -> PagedAttnCache:
    """``kv_dtype`` None/"bf16" keeps full-precision pools; "int8" stores
    per-page symmetric-quantized pools with f32 scale metadata (see
    :class:`PagedAttnCache`). ``scale_granularity`` picks the scale shape
    ("page_head" → one scale per (page, kv head), "page" → one per page)
    and ``hot_pages`` > 0 allocates the mixed-precision hot-resident
    overlay."""
    quant = kv_dtype not in (None, "bf16")
    if quant and kv_dtype != "int8":
        raise ValueError(f"unsupported kv_dtype {kv_dtype!r}")
    pool_dtype = jnp.int8 if quant else dtype
    extra = {}
    if quant:
        sh = num_kv if scale_granularity == "page_head" else 1
        extra = dict(
            k_scale=jnp.zeros((num_pages, sh), jnp.float32),
            v_scale=jnp.zeros((num_pages, sh), jnp.float32))
        if hot_pages > 0:
            extra.update(
                k_hot=jnp.zeros((hot_pages, num_kv, page_size, dk), dtype),
                v_hot=jnp.zeros((hot_pages, num_kv, page_size, dv), dtype),
                hot_ids=jnp.full((hot_pages,), -1, jnp.int32))
    return PagedAttnCache(
        k_pool=jnp.zeros((num_pages, num_kv, page_size, dk), pool_dtype),
        v_pool=jnp.zeros((num_pages, num_kv, page_size, dv), pool_dtype),
        pos_pool=jnp.full((num_pages, page_size), -1, jnp.int32),
        acc_pool=jnp.zeros((num_pages, num_kv, page_size), jnp.float32),
        page_table=jnp.full((batch, pages_per_lane), -1, jnp.int32),
        count=jnp.zeros((batch,), jnp.int32),
        **extra,
    )


def dequant_pages(pool: jax.Array, scale: jax.Array,
                  dtype=jnp.float32) -> jax.Array:
    """int8 pages (..., KV, ps, D) × per-page scales (..., SH) -> dtype.
    SH broadcasts over KV when the granularity is one-scale-per-page."""
    return (pool.astype(jnp.float32)
            * scale[..., :, None, None]).astype(dtype)


def quantize_tokens(x: jax.Array, scale: jax.Array) -> jax.Array:
    """float tokens (..., D) / scales broadcastable to ``x[..., 0]`` ->
    int8. Zero scale (unwritten page / all-zero content) quantizes to 0
    instead of dividing by zero."""
    s = jnp.where(scale > 0.0, scale, 1.0)
    q = jnp.round(x.astype(jnp.float32) / s[..., None])
    return jnp.clip(q, -QUANT_MAX, QUANT_MAX).astype(jnp.int8)


def _page_scales(tok: jax.Array, ps: int, sh: int) -> jax.Array:
    """Per-page scales for (T, KV, D) float tokens laid out from a page
    boundary -> (ceil(T/ps), SH); the partial last page pads with zeros
    (which never grow the amax)."""
    t, kvh, d = tok.shape
    npg = -(-t // ps)
    x = jnp.abs(tok.astype(jnp.float32))
    x = jnp.pad(x, ((0, npg * ps - t), (0, 0), (0, 0)))
    amax = x.reshape(npg, ps, kvh, d).max(axis=(1, 3))   # (NPG, KV)
    if sh == 1:
        amax = amax.max(axis=-1, keepdims=True)
    return amax / QUANT_MAX


def _insert_quant_token(pool: jax.Array, scale: jax.Array, phys: jax.Array,
                        off: jax.Array, x_new: jax.Array
                        ) -> Tuple[jax.Array, jax.Array]:
    """Quantized single-token insert with a per-page *running* scale:
    grow the page's scale to cover the new token's amax (requantizing the
    already-stored page ints when it grows — round-trip error stays one
    rounding step per growth) and write the quantized token. ``phys``
    (B,) already encodes suppressed rows as the out-of-bounds page."""
    x = x_new.astype(jnp.float32)                        # (B, KV, D)
    amax = jnp.abs(x).max(axis=-1)                       # (B, KV)
    if scale.shape[1] == 1:
        amax = amax.max(axis=-1, keepdims=True)          # (B, 1)
    safe = jnp.minimum(phys, pool.shape[0] - 1)
    s_old = scale[safe]                                  # (B, SH)
    s_cand = jnp.maximum(s_old, amax / QUANT_MAX)
    ratio = jnp.where(s_cand > 0.0, s_old / s_cand, 1.0)
    page = pool[safe].astype(jnp.float32)                # (B, KV, ps, D)
    requant = jnp.clip(jnp.round(page * ratio[:, :, None, None]),
                       -QUANT_MAX, QUANT_MAX).astype(pool.dtype)
    pool = pool.at[phys].set(requant, mode="drop")
    pool = pool.at[phys, :, off].set(quantize_tokens(x, s_cand), mode="drop")
    scale = scale.at[phys].set(s_cand, mode="drop")
    return pool, scale


def _demote_residents(hot_ids: jax.Array, freed_phys: jax.Array) -> jax.Array:
    """Drop hot residents whose physical page appears in ``freed_phys``
    (1-D, out-of-bounds entries never match): recycled pages must not
    serve a stale full-precision overlay."""
    stale = (hot_ids[:, None] == freed_phys[None, :]).any(axis=1)
    return jnp.where(stale, -1, hot_ids)


def _hot_overlay(vals: jax.Array, hot_pool: jax.Array, table: jax.Array,
                 hot_ids: jax.Array) -> jax.Array:
    """Overlay resident pages onto dequantized gathers: vals (B, NP, KV,
    ps, D) with page table (B, NP); resident pages (table entry matching a
    live ``hot_ids`` slot) read the exact ``hot_pool`` copy instead."""
    m = (table[..., None] == hot_ids) & (hot_ids >= 0)   # (B, NP, H)
    hit = m.any(axis=-1)
    hidx = jnp.argmax(m, axis=-1)
    hot = hot_pool.astype(vals.dtype)[hidx]              # (B, NP, KV, ps, D)
    return jnp.where(hit[..., None, None, None], hot, vals)


def _gather_pool(pool: jax.Array, table: jax.Array) -> jax.Array:
    """(P, ...) pool × (B, NP) table -> (B, NP, ...) gathered pages.
    Unmapped entries (-1) gather page 0; callers mask them via positions
    (which :func:`gather_positions` forces to -1 for unmapped pages)."""
    return pool[jnp.maximum(table, 0)]


def gather_positions(cache: PagedAttnCache) -> jax.Array:
    """(B, S_log) int32 logical-slot positions (-1 for empty/unmapped)."""
    b = cache.page_table.shape[0]
    pos = _gather_pool(cache.pos_pool, cache.page_table)  # (B, NP, ps)
    pos = jnp.where(cache.page_table[..., None] >= 0, pos, -1)
    return pos.reshape(b, cache.num_slots)


def paged_lane_view(cache: PagedAttnCache) -> AttnCache:
    """Materialize the per-lane contiguous view of a paged cache.

    The returned :class:`AttnCache` is slot-for-slot identical to what the
    contiguous cache would hold, so every reference attention core (and
    the shard_map-wrapped decode core) runs unchanged — this is the
    masked-dense/jnp fallback contract for paged serving. The Pallas
    decode kernel instead reads each lane's pages from the pool through
    the page table (kernels/aqua_decode.aqua_paged_decode_attention) and
    never pays this gather.
    """
    b = cache.page_table.shape[0]
    s = cache.num_slots
    k = _gather_pool(cache.k_pool, cache.page_table)      # (B,NP,KV,ps,Dk)
    v = _gather_pool(cache.v_pool, cache.page_table)
    if cache.k_scale is not None:
        k = dequant_pages(k, _gather_pool(cache.k_scale, cache.page_table))
        v = dequant_pages(v, _gather_pool(cache.v_scale, cache.page_table))
        if cache.k_hot is not None:
            k = _hot_overlay(k, cache.k_hot, cache.page_table, cache.hot_ids)
            v = _hot_overlay(v, cache.v_hot, cache.page_table, cache.hot_ids)
    acc = _gather_pool(cache.acc_pool, cache.page_table)  # (B,NP,KV,ps)
    kvh = k.shape[2]
    k = k.transpose(0, 2, 1, 3, 4).reshape(b, kvh, s, k.shape[-1])
    v = v.transpose(0, 2, 1, 3, 4).reshape(b, kvh, s, v.shape[-1])
    acc = acc.transpose(0, 2, 1, 3).reshape(b, kvh, s)
    return AttnCache(k=k, v=v, positions=gather_positions(cache),
                     count=cache.count, acc_score=acc)


def paged_lane_pages(cache: PagedAttnCache, lane: jax.Array,
                     dtype=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Gather one lane's mapped pages as a contiguous (dequantized) view:
    ``(k (1, KV, S_log, Dk), v (1, KV, S_log, Dv), positions (1, S_log))``.
    The prefix-shared / chunked prefill path reads the already-written
    prefix through this, so quantization stays a storage detail of the
    pool. Unmapped pages read position -1 (masked by attention)."""
    tbl = cache.page_table[lane]                         # (NP,)
    phys = jnp.maximum(tbl, 0)
    pk = cache.k_pool[phys]                              # (NP, KV, ps, Dk)
    pv = cache.v_pool[phys]
    if cache.k_scale is not None:
        out_dt = jnp.float32 if dtype is None else dtype
        pk = dequant_pages(pk, cache.k_scale[phys], out_dt)
        pv = dequant_pages(pv, cache.v_scale[phys], out_dt)
        if cache.k_hot is not None:
            pk = _hot_overlay(pk[None], cache.k_hot, tbl[None],
                              cache.hot_ids)[0]
            pv = _hot_overlay(pv[None], cache.v_hot, tbl[None],
                              cache.hot_ids)[0]
    elif dtype is not None:
        pk = pk.astype(dtype)
        pv = pv.astype(dtype)
    ppos = cache.pos_pool[phys]                          # (NP, ps)
    ppos = jnp.where(tbl[:, None] >= 0, ppos, -1)
    kvh = pk.shape[1]
    s_log = cache.num_slots
    pk = pk.transpose(1, 0, 2, 3).reshape(1, kvh, s_log, -1)
    pv = pv.transpose(1, 0, 2, 3).reshape(1, kvh, s_log, -1)
    return pk, pv, ppos.reshape(1, s_log)


def paged_select_slot(cache: PagedAttnCache, *, window: Optional[int],
                      h2o: bool, recent_len: int
                      ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Paged twin of :func:`select_slot`.

    Returns ``(slot (B,), evict_page (B,) | None)``. Full-cache and ring
    policies are arithmetic-identical to the contiguous cache (the page
    table only redirects storage). H2O eviction is *page-granular*: while
    the lane still has empty slots the first one is filled; once full, the
    whole page with the smallest accumulated score (stale-first under a
    combined window, recent pages protected) is freed — ``evict_page`` is
    its logical index (-1 = no eviction this step) and the incoming token
    lands in its first slot. :func:`paged_insert` clears the victim page.
    """
    b, npl = cache.page_table.shape
    ps = cache.page_size
    s_log = cache.num_slots
    count = cache.count
    if window is not None and not h2o:
        return count % s_log, None
    if not h2o:
        return jnp.minimum(count, s_log - 1), None
    pos = gather_positions(cache)                       # (B, S_log)
    cur = count
    empty = pos < 0
    has_empty = empty.any(axis=-1)
    first_empty = jnp.argmax(empty, axis=-1).astype(jnp.int32)
    protected = pos > (cur[:, None] - recent_len)       # recent tokens
    page_prot = protected.reshape(b, npl, ps).any(axis=-1)
    acc = _gather_pool(cache.acc_pool, cache.page_table)  # (B,NP,KV,ps)
    score = acc.sum(axis=(2, 3))                        # (B, NP)
    score = jnp.where(page_prot, jnp.inf, score)
    if window is not None:
        stale = (pos >= 0) & (pos <= cur[:, None] - window)
        page_stale = stale.reshape(b, npl, ps).all(axis=-1)
        score = jnp.where(page_stale & ~page_prot, -jnp.inf, score)
    victim = jnp.argmin(score, axis=-1).astype(jnp.int32)
    slot = jnp.where(has_empty, first_empty, victim * ps)
    evict = jnp.where(has_empty, -1, victim)
    return slot, evict


@jax.named_scope("kv.write")
def paged_insert(cache: PagedAttnCache, slot: jax.Array, k_new: jax.Array,
                 v_new: jax.Array, write_mask: Optional[jax.Array] = None,
                 evict_page: Optional[jax.Array] = None) -> PagedAttnCache:
    """Write one token's (projected/sliced) k, v at logical ``slot``.

    Physical addressing goes through the page table; suppressed writes
    (``write_mask`` False rows, unmapped pages) are redirected to an
    out-of-bounds page index and dropped (``mode="drop"``) so frozen
    lanes cost no extra HBM traffic. ``evict_page`` (page-granular H2O):
    the victim page's positions/scores are cleared *before* the write, so
    freed slots read as empty from the next step on.
    """
    b, _ = cache.page_table.shape
    ps = cache.page_size
    oob = cache.num_pages                      # dropped scatter destination
    rows = jnp.arange(b)
    entry = cache.page_table[rows, slot // ps]
    ok = entry >= 0
    if write_mask is not None:
        ok &= write_mask
    phys = jnp.where(ok, entry, oob)
    off = slot % ps

    pos_pool, acc_pool = cache.pos_pool, cache.acc_pool
    extra = {}
    if evict_page is not None:
        ev_entry = cache.page_table[rows, jnp.maximum(evict_page, 0)]
        ev_ok = (evict_page >= 0) & (ev_entry >= 0)
        if write_mask is not None:
            ev_ok &= write_mask
        ev_phys = jnp.where(ev_ok, ev_entry, oob)
        pos_pool = pos_pool.at[ev_phys].set(-1, mode="drop")
        acc_pool = acc_pool.at[ev_phys].set(0.0, mode="drop")
        if cache.k_scale is not None:
            extra["k_scale"] = cache.k_scale.at[ev_phys].set(0.0, mode="drop")
            extra["v_scale"] = cache.v_scale.at[ev_phys].set(0.0, mode="drop")
        if cache.hot_ids is not None:
            extra["hot_ids"] = _demote_residents(cache.hot_ids, ev_phys)

    if cache.k_scale is None:
        k_pool = cache.k_pool.at[phys, :, off].set(
            k_new.astype(cache.k_pool.dtype), mode="drop")
        v_pool = cache.v_pool.at[phys, :, off].set(
            v_new.astype(cache.v_pool.dtype), mode="drop")
    else:
        k_pool, extra["k_scale"] = _insert_quant_token(
            cache.k_pool, extra.get("k_scale", cache.k_scale), phys, off,
            k_new)
        v_pool, extra["v_scale"] = _insert_quant_token(
            cache.v_pool, extra.get("v_scale", cache.v_scale), phys, off,
            v_new)
        if cache.hot_ids is not None:
            # write-through: resident pages also get the exact value, so
            # the hot overlay never lags the authoritative int8 pool.
            hot_ids = extra.get("hot_ids", cache.hot_ids)
            hm = hot_ids[None, :] == phys[:, None]       # (B, H)
            hslot = jnp.where(hm.any(axis=1), jnp.argmax(hm, axis=1),
                              hot_ids.shape[0])
            extra["k_hot"] = cache.k_hot.at[hslot, :, off].set(
                k_new.astype(cache.k_hot.dtype), mode="drop")
            extra["v_hot"] = cache.v_hot.at[hslot, :, off].set(
                v_new.astype(cache.v_hot.dtype), mode="drop")
    pos_pool = pos_pool.at[phys, off].set(cache.count, mode="drop")
    acc_pool = acc_pool.at[phys, :, off].set(0.0, mode="drop")
    adv = jnp.int32(1) if write_mask is None else write_mask.astype(jnp.int32)
    return dataclasses.replace(cache, k_pool=k_pool, v_pool=v_pool,
                               pos_pool=pos_pool, acc_pool=acc_pool,
                               count=cache.count + adv, **extra)


def paged_accumulate_h2o(cache: PagedAttnCache, attn_weights: jax.Array,
                         write_mask: Optional[jax.Array] = None
                         ) -> PagedAttnCache:
    """Scatter-add the H2O statistic through the page table.

    attn_weights: (B, KV, G, S_log) probabilities over the *logical* slot
    view (what the reference decode core emits for the gathered lane
    view); summed over the G query heads per kv group. Invalid/unmapped
    slots carry zero weight (masked softmax) and unmapped pages are
    dropped scatters, so no page is polluted. Prefix-shared pages are
    incompatible with H2O (the engine rejects the combination), so no two
    lanes scatter into the same physical page.
    """
    b, npl = cache.page_table.shape
    ps = cache.page_size
    upd = attn_weights.astype(jnp.float32).sum(axis=2)  # (B, KV, S_log)
    if write_mask is not None:
        upd = jnp.where(write_mask[:, None, None], upd, 0.0)
    phys = jnp.where(cache.page_table >= 0, cache.page_table,
                     cache.num_pages)                   # (B, NP)
    phys_slot = jnp.repeat(phys, ps, axis=1)            # (B, S_log)
    off = jnp.tile(jnp.arange(ps, dtype=jnp.int32), npl)
    acc = cache.acc_pool.at[phys_slot, :, off].add(
        upd.transpose(0, 2, 1), mode="drop")
    return dataclasses.replace(cache, acc_pool=acc)


@jax.named_scope("kv.write")
def paged_graft(cache: PagedAttnCache, req: AttnCache, lane: jax.Array,
                num_slots: int) -> PagedAttnCache:
    """Copy logical slots ``[0, num_slots)`` of a B=1 contiguous cache
    (an admission prefill) into ``lane``'s pages of the paged cache.

    Every page currently mapped by the lane is cleared first (positions
    -1, scores 0) — pool pages are recycled across requests, so stale
    positions from a previous tenant must never read as valid. The page
    table row itself is written host-side by the allocator *before* the
    jitted admission step runs (see serving.engine); this function only
    moves cache content. ``num_slots`` is static (one compile per prompt
    bucket).
    """
    ps = cache.page_size
    oob = cache.num_pages
    tbl = cache.page_table[lane]                        # (NP,)
    all_phys = jnp.where(tbl >= 0, tbl, oob)
    pos_pool = cache.pos_pool.at[all_phys].set(-1, mode="drop")
    acc_pool = cache.acc_pool.at[all_phys].set(0.0, mode="drop")

    idx = jnp.arange(num_slots)
    entry = tbl[idx // ps]
    phys = jnp.where(entry >= 0, entry, oob)
    off = idx % ps
    k_tok = req.k[0][:, idx].transpose(1, 0, 2)         # (T, KV, Dk)
    v_tok = req.v[0][:, idx].transpose(1, 0, 2)
    extra = {}
    if cache.k_scale is None:
        k_pool = cache.k_pool.at[phys, :, off].set(
            k_tok.astype(cache.k_pool.dtype), mode="drop")
        v_pool = cache.v_pool.at[phys, :, off].set(
            v_tok.astype(cache.v_pool.dtype), mode="drop")
    else:
        # per-page scales over the grafted prompt, stale scales cleared
        # for every recycled page the lane maps beyond the prompt
        k_scale = cache.k_scale.at[all_phys].set(0.0, mode="drop")
        v_scale = cache.v_scale.at[all_phys].set(0.0, mode="drop")
        ks = _page_scales(k_tok, ps, k_scale.shape[1])  # (NPG, SH)
        vs = _page_scales(v_tok, ps, v_scale.shape[1])
        npg = ks.shape[0]
        pg_phys = jnp.where(tbl[:npg] >= 0, tbl[:npg], oob)
        extra["k_scale"] = k_scale.at[pg_phys].set(ks, mode="drop")
        extra["v_scale"] = v_scale.at[pg_phys].set(vs, mode="drop")
        k_pool = cache.k_pool.at[phys, :, off].set(
            quantize_tokens(k_tok, ks[idx // ps]), mode="drop")
        v_pool = cache.v_pool.at[phys, :, off].set(
            quantize_tokens(v_tok, vs[idx // ps]), mode="drop")
        if cache.hot_ids is not None:
            # H2O precision policy: the lane's freshest page is the
            # hottest (recency-protected by eviction); promote it to a
            # full-precision residency, evicting the lowest accumulated
            # score resident. Stale residents on recycled pages drop.
            hot_ids = _demote_residents(cache.hot_ids, all_phys)
            lp = (num_slots - 1) // ps
            new_page = tbl[lp]
            res_score = jnp.where(
                hot_ids >= 0,
                acc_pool[jnp.maximum(hot_ids, 0)].sum(axis=(1, 2)),
                -jnp.inf)
            victim = jnp.argmin(res_score).astype(jnp.int32)
            vslot = jnp.where(new_page >= 0, victim, hot_ids.shape[0])
            extra["hot_ids"] = hot_ids.at[vslot].set(new_page, mode="drop")
            pad = (lp + 1) * ps - num_slots
            k_seg = jnp.pad(req.k[0][:, lp * ps:num_slots],
                            ((0, 0), (0, pad), (0, 0)))
            v_seg = jnp.pad(req.v[0][:, lp * ps:num_slots],
                            ((0, 0), (0, pad), (0, 0)))
            extra["k_hot"] = cache.k_hot.at[vslot].set(
                k_seg.astype(cache.k_hot.dtype), mode="drop")
            extra["v_hot"] = cache.v_hot.at[vslot].set(
                v_seg.astype(cache.v_hot.dtype), mode="drop")
    pos_pool = pos_pool.at[phys, off].set(req.positions[0, idx], mode="drop")
    acc_pool = acc_pool.at[phys, :, off].set(
        req.acc_score[0][:, idx].transpose(1, 0), mode="drop")
    count = cache.count.at[lane].set(req.count[0])
    return dataclasses.replace(cache, k_pool=k_pool, v_pool=v_pool,
                               pos_pool=pos_pool, acc_pool=acc_pool,
                               count=count, **extra)


@jax.named_scope("kv.write")
def paged_write_tail(cache: PagedAttnCache, lane: jax.Array,
                     k_tail: jax.Array, v_tail: jax.Array,
                     positions: jax.Array, start_page: jax.Array,
                     new_count: jax.Array) -> PagedAttnCache:
    """Write a prefix-shared admission's *tail* K/V into ``lane``'s
    private pages, leaving the shared prefix pages untouched.

    k_tail (T, KV, Dk) / v_tail (T, KV, Dv) / positions (T,) start at the
    (page-aligned) divergence point; ``start_page`` is its logical page
    index. Tail/decode pages are cleared first (pool recycling), shared
    pages (< start_page) are read-only by construction.
    """
    ps = cache.page_size
    oob = cache.num_pages
    tbl = cache.page_table[lane]                        # (NP,)
    npl = tbl.shape[0]
    private = jnp.arange(npl) >= start_page
    clear_phys = jnp.where(private & (tbl >= 0), tbl, oob)
    pos_pool = cache.pos_pool.at[clear_phys].set(-1, mode="drop")
    acc_pool = cache.acc_pool.at[clear_phys].set(0.0, mode="drop")

    t = k_tail.shape[0]
    idx = start_page * ps + jnp.arange(t)
    entry = tbl[idx // ps]
    phys = jnp.where(entry >= 0, entry, oob)
    off = idx % ps
    extra = {}
    if cache.k_scale is None:
        k_pool = cache.k_pool.at[phys, :, off].set(
            k_tail.astype(cache.k_pool.dtype), mode="drop")
        v_pool = cache.v_pool.at[phys, :, off].set(
            v_tail.astype(cache.v_pool.dtype), mode="drop")
    else:
        # the tail starts page-aligned, so per-page scales line up with
        # tbl[start_page + i]; shared prefix pages (< start_page) keep
        # the registrant's scales untouched.
        k_scale = cache.k_scale.at[clear_phys].set(0.0, mode="drop")
        v_scale = cache.v_scale.at[clear_phys].set(0.0, mode="drop")
        ks = _page_scales(k_tail, ps, k_scale.shape[1])  # (NPG, SH)
        vs = _page_scales(v_tail, ps, v_scale.shape[1])
        npg = ks.shape[0]
        pg_tbl = tbl[start_page + jnp.arange(npg)]
        pg_phys = jnp.where(pg_tbl >= 0, pg_tbl, oob)
        extra["k_scale"] = k_scale.at[pg_phys].set(ks, mode="drop")
        extra["v_scale"] = v_scale.at[pg_phys].set(vs, mode="drop")
        tpg = jnp.arange(t) // ps
        k_pool = cache.k_pool.at[phys, :, off].set(
            quantize_tokens(k_tail, ks[tpg]), mode="drop")
        v_pool = cache.v_pool.at[phys, :, off].set(
            quantize_tokens(v_tail, vs[tpg]), mode="drop")
        if cache.hot_ids is not None:
            extra["hot_ids"] = _demote_residents(cache.hot_ids, clear_phys)
    pos_pool = pos_pool.at[phys, off].set(positions, mode="drop")
    count = cache.count.at[lane].set(new_count)
    return dataclasses.replace(cache, k_pool=k_pool, v_pool=v_pool,
                               pos_pool=pos_pool, acc_pool=acc_pool,
                               count=count, **extra)


def paged_reset_lane(cache: PagedAttnCache, lane: jax.Array
                     ) -> PagedAttnCache:
    """Restore ``lane`` to the empty condition: clear its mapped pages,
    unmap the table row, zero its count. (Host-side page *deallocation*
    is the allocator's job; this clears device state.)"""
    oob = cache.num_pages
    tbl = cache.page_table[lane]
    phys = jnp.where(tbl >= 0, tbl, oob)
    extra = {}
    if cache.k_scale is not None:
        extra["k_scale"] = cache.k_scale.at[phys].set(0.0, mode="drop")
        extra["v_scale"] = cache.v_scale.at[phys].set(0.0, mode="drop")
    if cache.hot_ids is not None:
        extra["hot_ids"] = _demote_residents(cache.hot_ids, phys)
    return dataclasses.replace(
        cache,
        pos_pool=cache.pos_pool.at[phys].set(-1, mode="drop"),
        acc_pool=cache.acc_pool.at[phys].set(0.0, mode="drop"),
        page_table=cache.page_table.at[lane].set(-1),
        count=cache.count.at[lane].set(0), **extra)


def paged_copy_page(cache: PagedAttnCache, src: jax.Array, dst: jax.Array
                    ) -> PagedAttnCache:
    """Device-side companion of the host allocator's copy-on-write
    ``PagePool.make_private``: duplicate physical page ``src`` into the
    freshly-reserved ``dst``. K/V content, positions, H2O scores and (for
    quantized pools) the per-page scale metadata ride together, so a
    privatized copy dequantizes bit-identically to the shared original."""
    cp = lambda pool: pool.at[dst].set(pool[src])
    extra = {}
    if cache.k_scale is not None:
        extra = dict(k_scale=cp(cache.k_scale), v_scale=cp(cache.v_scale))
    return dataclasses.replace(
        cache, k_pool=cp(cache.k_pool), v_pool=cp(cache.v_pool),
        pos_pool=cp(cache.pos_pool), acc_pool=cp(cache.acc_pool), **extra)


def tree_bytes(tree) -> int:
    """Total bytes of a pytree of (abstract or concrete) arrays — the
    single source of truth for cache-footprint accounting (both serving
    engines' ``cache_bytes`` and the benches go through this)."""
    return sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# SSM / recurrent caches
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclass
class SSMCache:
    """Mamba-2 per-layer state: rolling conv window + SSD state."""

    conv: jax.Array   # (B, conv_width-1, conv_channels)
    state: jax.Array  # (B, nheads, head_dim, state_dim)
    count: jax.Array  # (B,)


@jax.tree_util.register_dataclass
@dataclass
class RGLRUCache:
    """RecurrentGemma recurrent-block state."""

    conv: jax.Array   # (B, conv_width-1, lru_width)
    state: jax.Array  # (B, lru_width) real-gated LRU hidden state
    count: jax.Array
