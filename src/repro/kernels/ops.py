"""jit'd public wrappers around the Pallas kernels.

``aqua_decode`` / ``aqua_prefill`` take model-layout tensors (seq-major
cache), handle the dim-major restructuring, padding, query-block gathering
and top-k selection, and dispatch to the kernels. ``interpret=None``
auto-resolves via :mod:`repro.runtime_flags` — compiled on TPU,
interpreted elsewhere — so the same call sites serve production and CI.

Dim-major cache layout contract (shared with ``repro.core.kvcache``):
the projected key cache is stored seq-major ``(B, KV, S, D)`` at the
model layer and viewed dim-major ``(B, KV, NB, bd, S)`` by the kernels,
where ``NB = D // bd`` dim-blocks of ``bd`` sublanes each span the full
lane-dim sequence stripe. Magnitude selection picks whole dim-blocks, so
the kernels stream only the selected ``NB_sel`` stripes HBM→VMEM. The
block-paged pool is seq-major per page, ``(P, KV, page_size, D)``, and
:func:`aqua_paged_decode` reads those pages whole, all G query heads of a
KV head at once, with q masked by the selection: no relayout per step.

Shard-local contract (mesh-native serving): these wrappers are also the
bodies run inside ``shard_map`` by ``repro.core.attention`` — every
shape they see is then *shard-local* (lanes partitioned over the data
axes, KV heads — and their query groups — over ``model``). That works
without changes because nothing here crosses the batch or head axes: the
top-k block-index tables are computed per (row, head), the sequence and
dim axes arrive whole per shard, and the per-shard ``NB_total``/
``NB_sel`` accounting equals the global one (:func:`block_counts`).

The paged wrapper extends the same contract: page-*table* rows are
shard-local (they partition with their lanes over the data axes), while
the page *pool* arrives with its page axis whole on every data shard —
pages are lane-global, any lane may map any physical page, so the
shard-local table entries are pool-global page ids that dereference
unchanged inside the kernel. Only the pool's KV-head axis is
shard-local (partitioned over ``model``, whole pages riding with their
head); no collective is ever needed between the table lookup and the
page DMA.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import aqua as aqua_lib
from repro.core.aqua import ceil_to as _ceil_to
from repro.kernels.aqua_decode import (
    RUN_TOKENS, aqua_decode_attention, aqua_paged_decode_attention)
from repro.kernels.aqua_prefill import aqua_prefill_attention
from repro.kernels.flash_attention import flash_attention  # noqa: F401


def to_dim_major_blocks(khat: jax.Array, block_dims: int) -> jax.Array:
    """(B, KV, S, D) seq-major -> (B, KV, NB, bd, S) dim-major blocks.

    In production this is the *storage layout* of the projected key cache
    (written incrementally at insert time); here it is a transpose helper
    for tests/benchmarks entering from the model layout.
    """
    b, kvh, s, d = khat.shape
    assert d % block_dims == 0, (d, block_dims)
    nb = d // block_dims
    kt = khat.transpose(0, 1, 3, 2)                 # (B, KV, D, S)
    return kt.reshape(b, kvh, nb, block_dims, s)


def round_k_dims(d: int, k_ratio: float, block_dims: int) -> int:
    """Kept-dim count for a k_ratio: rounded to the nearest dim count, then
    up to a whole number of dim-blocks, clamped to [block_dims, d]. The
    single source of truth shared by the kernel wrappers, oracles and
    benchmarks."""
    k_dims = max(block_dims, int(round(k_ratio * d)))
    k_dims = ((k_dims + block_dims - 1) // block_dims) * block_dims
    return min(k_dims, d)


def block_counts(d: int, k_ratio: float, block_dims: int) -> tuple:
    """(NB_total, NB_sel) dim-block accounting for head dim ``d``.

    Shard-local and global accounting coincide under the serving mesh:
    ``shard_map`` partitions lanes and KV heads, never the dim axis, so
    every shard holds all ``NB_total`` dim-blocks of its heads' K̂ stripes
    and selects the same ``NB_sel`` of them. Used by the benchmarks'
    HBM-byte ratios so they stay honest for the mesh rows too."""
    return d // block_dims, round_k_dims(d, k_ratio, block_dims) // block_dims


@functools.partial(jax.jit, static_argnames=("k_ratio", "block_dims",
                                             "seq_blk", "scale",
                                             "interpret"))
def aqua_decode(q_hat: jax.Array, khat: jax.Array, v: jax.Array,
                lengths: jax.Array, *, k_ratio: float = 0.75,
                block_dims: int = 8, seq_blk: int = 128,
                scale: Optional[float] = None,
                interpret: Optional[bool] = None) -> jax.Array:
    """End-to-end AQUA decode attention (selection + kernel).

    q_hat: (B, H, D) projected query; khat: (B, KV, S, D) projected key
    cache (seq-major model layout); v: (B, KV, S, Dv); lengths: (B,).
    """
    b, h, d = q_hat.shape
    s = khat.shape[2]
    nb = d // block_dims
    k_dims = round_k_dims(d, k_ratio, block_dims)

    with jax.named_scope("aqua.select"):
        block_idx = aqua_lib.topk_block_indices(q_hat, k_dims, block_dims)
        # gather the selected q blocks (tiny: H × k elements)
        qb = q_hat.reshape(b, h, nb, block_dims)
        q_sel = jnp.take_along_axis(qb, block_idx[..., None], axis=2)

    with jax.named_scope("aqua.kv_layout"):
        pad = (-s) % seq_blk
        if pad:
            khat = jnp.pad(khat, ((0, 0), (0, 0), (0, pad), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        khat_blocks = to_dim_major_blocks(khat, block_dims)
    return aqua_decode_attention(q_sel, khat_blocks, v, block_idx, lengths,
                                 block_dims=block_dims, seq_blk=seq_blk,
                                 scale=scale, interpret=interpret)


def pages_per_step(page_size: int, lane_pages: int) -> int:
    """Pages of one lane a grid step of the whole-page decode body reads:
    up to ``RUN_TOKENS`` tokens, at most the lane's pages."""
    return max(1, min(lane_pages, RUN_TOKENS // page_size))


def attended_pages(page_table: jax.Array, lengths: jax.Array,
                   part_idx: Optional[jax.Array], page_size: int) -> tuple:
    """The whole-page body's per-lane page list: (ids (B, NE) physical
    pages in attention order, n (B,) entries that hold tokens, tails (B,)
    tokens in the n-th entry). Without ``part_idx`` the list is the page
    table; with it, entry i is logical page ``part_idx[b, i]`` (sorted
    ascending, so the entries holding tokens come first)."""
    used = (lengths + page_size - 1) // page_size
    if part_idx is None:
        ids, n, last = page_table, used, used - 1
    else:
        ids = jnp.take_along_axis(page_table, part_idx, axis=1)
        n = jnp.sum(part_idx < used[:, None], axis=1)
        last = jnp.take_along_axis(part_idx, jnp.maximum(n - 1, 0)[:, None],
                                   axis=1)[:, 0]
    tails = jnp.clip(lengths - last * page_size, 0, page_size)
    return (jnp.maximum(ids, 0).astype(jnp.int32), n.astype(jnp.int32),
            tails.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("k_ratio", "block_dims",
                                             "scale", "interpret"))
def aqua_paged_decode(q_hat: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      page_table: jax.Array, lengths: jax.Array,
                      k_scale: Optional[jax.Array] = None,
                      v_scale: Optional[jax.Array] = None,
                      part_idx: Optional[jax.Array] = None,
                      block_idx: Optional[jax.Array] = None, *,
                      k_ratio: float = 0.75, block_dims: int = 8,
                      scale: Optional[float] = None,
                      interpret: Optional[bool] = None) -> jax.Array:
    """End-to-end AQUA decode attention over a *paged* KV pool.

    q_hat: (B, H, D) projected query; k_pool: (P, KV, ps, D) projected key
    page pool (seq-major per page); v_pool: (P, KV, ps, Dv);
    page_table: (B, NP_lane) int32 (-1 unmapped); lengths: (B,).
    k_scale/v_scale: (P, SH) f32 per-page scales when the pools are int8
    quantized (None for full precision) — threaded to the kernel as extra
    scalar-prefetch operands, where the key scale folds into the softmax
    scale (dequant-free score accumulation).
    part_idx: (B, KP) int32 stage-1 participating logical pages per lane
    (``core.selection.participating_pages``), or None for all pages —
    hierarchical AQUA's token-sparsity table, composed into each lane's
    page list (:func:`attended_pages`); the kernel walks only those KP
    pages. block_idx: precomputed (B, H, NB_sel) stage-2 dim-block
    selection (a ``SelectionPlan``'s), or None to select here from
    ``q_hat`` magnitudes.

    Same magnitude selection as :func:`aqua_decode`, applied as a mask:
    each head's unselected dim-blocks of q̂ are zeroed, and the kernel
    reads the pool's own seq-major pages, only those that hold tokens
    (:func:`~repro.kernels.aqua_decode.aqua_paged_decode_attention`). No
    gathered contiguous view is ever materialized.
    """
    b, h, d = q_hat.shape
    ps = k_pool.shape[2]
    nb = d // block_dims
    k_dims = round_k_dims(d, k_ratio, block_dims)

    with jax.named_scope("aqua.select"):
        if block_idx is None:
            block_idx = aqua_lib.topk_block_indices(q_hat, k_dims,
                                                    block_dims)
        keep = jnp.zeros((b, h, nb), q_hat.dtype)
        keep = jnp.put_along_axis(keep, block_idx, 1, axis=-1, inplace=False)
        q_in = q_hat * jnp.repeat(keep, block_dims, axis=-1)

    ids, n, tails = attended_pages(page_table, lengths, part_idx, ps)
    out = aqua_paged_decode_attention(
        q_in, k_pool, v_pool, ids, n, tails, k_scale, v_scale,
        pages_per_step=pages_per_step(ps, ids.shape[1]),
        scale=d ** -0.5 if scale is None else scale, interpret=interpret)
    return out if k_scale is not None else out.astype(v_pool.dtype)


@functools.partial(jax.jit, static_argnames=("k_ratio", "block_dims",
                                             "q_blk", "k_blk", "causal",
                                             "window", "scale", "interpret"))
def aqua_prefill(q_hat: jax.Array, khat: jax.Array, v: jax.Array,
                 lengths: Optional[jax.Array] = None, *,
                 k_ratio: float = 0.75, block_dims: int = 8,
                 q_blk: int = 128, k_blk: int = 128, causal: bool = True,
                 window: Optional[int] = None,
                 scale: Optional[float] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """End-to-end AQUA block-sparse chunked-prefill attention.

    Queries are processed in seq-chunks of ``q_blk``; each chunk shares the
    dim-block set selected from its aggregated |q̂| magnitudes (see
    :func:`repro.core.aqua.chunk_topk_block_indices`), so only
    ``k_ratio`` of the dim-major key stripes are streamed per tile. The
    masked-dense oracle is :func:`repro.kernels.ref.aqua_prefill_ref`.

    q_hat: (B, H, S, D) projected queries (head-major kernel layout);
    khat: (B, KV, S, D) projected keys (seq-major); v: (B, KV, S, Dv);
    lengths: (B,) valid lengths (None -> all rows full). Returns
    (B, H, S, Dv); rows at/beyond a row's length are don't-care.
    """
    b, h, s, d = q_hat.shape
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)

    # clamp chunk sizes for short sequences, then pad S so both divide it
    q_blk = min(q_blk, _ceil_to(s, 8))
    k_blk = min(k_blk, _ceil_to(s, 8))
    spad = _ceil_to(s, math.lcm(q_blk, k_blk))
    pad = spad - s
    if pad:
        q_hat = jnp.pad(q_hat, ((0, 0), (0, 0), (0, pad), (0, 0)))
        khat = jnp.pad(khat, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nqc = spad // q_blk
    nb = d // block_dims
    k_dims = round_k_dims(d, k_ratio, block_dims)

    with jax.named_scope("aqua.select"):
        block_idx = aqua_lib.chunk_topk_block_indices(
            q_hat, k_dims, block_dims, q_blk, lengths)
        # gather selected q dim-blocks per chunk: (B,H,NQC,NB_sel,q_blk,bd)
        qb = q_hat.reshape(b, h, nqc, q_blk, nb, block_dims
                           ).transpose(0, 1, 2, 4, 3, 5)
        q_sel = jnp.take_along_axis(qb, block_idx[..., None, None], axis=3)

    with jax.named_scope("aqua.kv_layout"):
        khat_blocks = to_dim_major_blocks(khat, block_dims)
    out = aqua_prefill_attention(q_sel, khat_blocks, v, block_idx, lengths,
                                 block_dims=block_dims, q_blk=q_blk,
                                 k_blk=k_blk, causal=causal, window=window,
                                 scale=scale, interpret=interpret)
    return out[:, :, :s]


@functools.partial(jax.jit, static_argnames=("q_offset", "k_ratio",
                                             "block_dims", "q_blk", "k_blk",
                                             "causal", "window", "scale",
                                             "interpret"))
def aqua_prefill_chunk(q_hat: jax.Array, khat: jax.Array, v: jax.Array,
                       lengths: jax.Array, *, q_offset: int,
                       mag_state: Optional[jax.Array] = None,
                       k_ratio: float = 0.75, block_dims: int = 8,
                       q_blk: int = 128, k_blk: int = 128,
                       causal: bool = True, window: Optional[int] = None,
                       scale: Optional[float] = None,
                       interpret: Optional[bool] = None) -> tuple:
    """Chunk-resumable AQUA prefill: attention for query rows
    [q_offset, q_offset + T) against the key stripe [0, S).

    Masked-out key tiles are exact no-ops in the online softmax, so when
    every chunk boundary is a ``q_blk`` multiple the concatenated chunk
    outputs are **bitwise identical** to one monolithic
    :func:`aqua_prefill` call — each chunk runs the same tiles with the
    same dim-block selection. A ragged boundary (``q_offset % q_blk !=
    0``) is still numerically valid (tiles re-anchor at ``q_offset``) but
    only approximately equal, because the straddling tile aggregates |q̂|
    over a different row set; ``mag_state`` keeps the selection itself
    consistent across a ragged split.

    q_hat:     (B, H, T, D) projected queries for this chunk only
    khat:      (B, KV, S, D) projected keys, seq-major, covering at least
               rows [0, q_offset + T) — typically the whole cache stripe
    v:         (B, KV, S, Dv)
    lengths:   (B,) — valid *sequence* lengths (global positions; both the
               key mask and the |q̂| aggregation use them)
    q_offset:  static global row index of this chunk's first query
    mag_state: (B, H, NB_total) float32 running |q̂| block aggregate of a
               partially filled leading tile (from the previous chunk's
               carry), or None. Added to this chunk's first tile before
               selection.
    returns:   (out (B, H, T, Dv), carry (B, H, NB_total) float32) —
               ``carry`` is the trailing tile's |q̂| aggregate when
               ``T % q_blk != 0`` (feed it to the next chunk's
               ``mag_state``), else zeros.
    """
    b, h, t, d = q_hat.shape
    s = khat.shape[2]
    assert q_offset >= 0 and q_offset + t <= s, (q_offset, t, s)

    q_blk = min(q_blk, _ceil_to(t, 8))
    k_blk = min(k_blk, _ceil_to(s, 8))
    tpad = _ceil_to(t, q_blk)
    spad = _ceil_to(max(s, q_offset + tpad), k_blk)
    if tpad - t:
        q_hat = jnp.pad(q_hat, ((0, 0), (0, 0), (0, tpad - t), (0, 0)))
    if spad - s:
        khat = jnp.pad(khat, ((0, 0), (0, 0), (0, spad - s), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, spad - s), (0, 0)))
    nqc = tpad // q_blk
    nb = d // block_dims
    k_dims = round_k_dims(d, k_ratio, block_dims)
    kb = k_dims // block_dims

    with jax.named_scope("aqua.select"):
        # chunk-local |q̂| block aggregation — same math as
        # chunk_topk_block_indices but masked by *global* positions and
        # carrying the previous chunk's partial leading-tile aggregate
        mag = jnp.abs(q_hat.astype(jnp.float32))
        row = jnp.arange(tpad)
        valid = ((row[None, :] < t)
                 & (q_offset + row[None, :] < lengths[:, None]))
        mag = mag * valid[:, None, :, None]
        bmag = mag.reshape(b, h, nqc, q_blk, nb, block_dims
                           ).sum(axis=(3, 5))                # (B,H,NQC,NB)
        if mag_state is not None:
            bmag = bmag.at[:, :, 0, :].add(mag_state)
        if t % q_blk != 0:
            carry = bmag[:, :, -1, :]
        else:
            carry = jnp.zeros((b, h, nb), jnp.float32)
        _, bidx = jax.lax.top_k(bmag, kb)
        block_idx = jnp.sort(bidx, axis=-1).astype(jnp.int32)

        qb = q_hat.reshape(b, h, nqc, q_blk, nb, block_dims
                           ).transpose(0, 1, 2, 4, 3, 5)
        q_sel = jnp.take_along_axis(qb, block_idx[..., None, None], axis=3)

    with jax.named_scope("aqua.kv_layout"):
        khat_blocks = to_dim_major_blocks(khat, block_dims)
    out = aqua_prefill_attention(q_sel, khat_blocks, v, block_idx, lengths,
                                 block_dims=block_dims, q_blk=q_blk,
                                 k_blk=k_blk, causal=causal, window=window,
                                 scale=scale, interpret=interpret,
                                 q_offset=q_offset)
    return out[:, :, :t], carry
