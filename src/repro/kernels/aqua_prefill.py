"""AQUA block-sparse chunked-prefill Pallas TPU kernel.

Prefill counterpart of ``aqua_decode.py``: the projected key cache keeps the
same **dim-major** layout ``(B, KV, NB_total, bd, S)`` — dim-blocks of ``bd``
sublanes × a long lane-dim sequence stripe — and the magnitude-selected
dim-block indices are scalar-prefetched and dereferenced inside the K
BlockSpec ``index_map``. Queries are processed in causal seq-chunks of
``q_blk``: each chunk aggregates |q̂| per dim-block over its queries and the
top ``NB_sel`` blocks are shared by the whole chunk (the chunked
generalization of the paper's per-query selection; equal to it at
``q_blk=1``). Only ``NB_sel / NB_total = k_ratio`` of the key dim-blocks are
streamed HBM→VMEM per (query-chunk, key-chunk) tile, so the quadratic
score-read term — the cost the paper targets — drops to ``k_ratio`` of
dense flash attention.

The value product and online softmax are fused flash style; the (S, S)
score matrix never materializes in HBM. Causally dead (query-chunk,
key-chunk) tiles are skipped via ``pl.when`` so their partial dot products
cost nothing.

Grid: (B, H, num_q_chunks, num_k_chunks, NB_sel) — dim-block index j
innermost; the V block index_map is constant in j, so Pallas keeps the V
tile resident across the j loop (single fetch per key chunk).

Mesh-native serving runs this kernel *inside* ``shard_map``
(``repro.core.attention.shard_mapped_prefill_kernel``): B and H are then
shard-local extents (lanes over the data axes, KV heads + their query
groups over ``model``), while S and the dim-block axis arrive whole per
shard — each model shard streams whole dim-blocks of its own heads and
``NB_sel``/``NB_total`` are the same per shard as globally.

Paged serving contract: prefill attention itself reads only the prompt's
own q̂/K̂/V (never the pool), so this kernel runs unchanged for paged
admissions — the *writes* land in pool pages afterwards
(``kvcache.paged_graft`` scatters the B=1 prefill cache through the
lane's page table, ``kvcache.paged_write_tail`` the prefix-shared tail).
Only the decode kernel walks the page table at read time
(``aqua_decode.aqua_paged_decode_attention``), because only decode reads
a paged cache inside the hot loop; prefix-shared *tail* prefills read the
shared pages through the gathered lane view on the reference path
(admission-time, off the steady-state roofline).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime_flags as _rtf

NEG_INF = -1e30


def _kernel(idx_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            s_ref, m_ref, l_ref, acc_ref, *, scale: float, q_blk: int,
            k_blk: int, nb_sel: int, nkc: int, causal: bool,
            window: Optional[int], q_offset: int):
    bi = pl.program_id(0)
    qc = pl.program_id(2)
    kc = pl.program_id(3)
    j = pl.program_id(4)

    @pl.when((kc == 0) & (j == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Skip tiles that the causal / window band fully masks: the last query
    # of this chunk sits before the first key, or every key is staler than
    # the window of the first query. ``q_offset`` shifts query positions
    # for chunk-resumable invocations (queries are rows
    # [q_offset, q_offset + T) of the sequence whose keys span the stripe).
    live = kc >= 0
    if causal:
        live &= kc * k_blk <= q_offset + qc * q_blk + (q_blk - 1)
    if window is not None:
        live &= kc * k_blk + (k_blk - 1) > q_offset + qc * q_blk - window

    @pl.when(live & (j == 0))
    def _reset_scores():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(live)
    def _accumulate():
        # partial scores for this selected dim-block:
        # (q_blk, bd) @ (bd, k_blk)
        q_blkj = q_ref[0, 0, 0, 0].astype(jnp.float32)
        k_blkj = k_ref[0, 0, 0].astype(jnp.float32)
        s_ref[...] += jax.lax.dot_general(
            q_blkj, k_blkj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(live & (j == nb_sel - 1))
    def _finalize_tile():
        s = s_ref[...] * scale                       # (q_blk, k_blk)
        qpos = q_offset + qc * q_blk + jax.lax.broadcasted_iota(
            jnp.int32, (q_blk, k_blk), 0)
        kpos = kc * k_blk + jax.lax.broadcasted_iota(
            jnp.int32, (q_blk, k_blk), 1)
        mask = kpos < len_ref[bi]
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                          # (q_blk, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        v_blk = v_ref[0, 0].astype(jnp.float32)      # (k_blk, Dv)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when((kc == nkc - 1) & (j == nb_sel - 1))
    def _write():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)
                      )[None, None].astype(o_ref.dtype)


def _part_kernel(idx_ref, part_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                 s_ref, m_ref, l_ref, acc_ref, *, scale: float, q_blk: int,
                 k_blk: int, nb_sel: int, kt: int, causal: bool,
                 window: Optional[int], q_offset: int):
    """Hierarchical twin of :func:`_kernel` — the grid's key-chunk axis
    runs over *participating* k-tiles only; ``part_ref`` (B, NQC, KT)
    maps each grid step to its logical key chunk (per q-tile, sorted
    ascending, diagonal tiles pinned —
    ``core.selection.chunk_participating_tiles``). Dropped tiles' K̂/V
    bytes are never streamed. Causal/window masking uses the logical
    chunk, so the math on surviving tiles is identical to :func:`_kernel`
    visiting the same tiles."""
    bi = pl.program_id(0)
    qc = pl.program_id(2)
    kci = pl.program_id(3)
    j = pl.program_id(4)
    kc = part_ref[bi, qc, kci]                       # logical key chunk

    @pl.when((kci == 0) & (j == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = kc >= 0
    if causal:
        live &= kc * k_blk <= q_offset + qc * q_blk + (q_blk - 1)
    if window is not None:
        live &= kc * k_blk + (k_blk - 1) > q_offset + qc * q_blk - window

    @pl.when(live & (j == 0))
    def _reset_scores():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(live)
    def _accumulate():
        q_blkj = q_ref[0, 0, 0, 0].astype(jnp.float32)
        k_blkj = k_ref[0, 0, 0].astype(jnp.float32)
        s_ref[...] += jax.lax.dot_general(
            q_blkj, k_blkj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(live & (j == nb_sel - 1))
    def _finalize_tile():
        s = s_ref[...] * scale                       # (q_blk, k_blk)
        qpos = q_offset + qc * q_blk + jax.lax.broadcasted_iota(
            jnp.int32, (q_blk, k_blk), 0)
        kpos = kc * k_blk + jax.lax.broadcasted_iota(
            jnp.int32, (q_blk, k_blk), 1)
        mask = kpos < len_ref[bi]
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                          # (q_blk, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        v_blk = v_ref[0, 0].astype(jnp.float32)      # (k_blk, Dv)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when((kci == kt - 1) & (j == nb_sel - 1))
    def _write():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)
                      )[None, None].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_dims", "q_blk", "k_blk",
                                             "causal", "window", "scale",
                                             "interpret", "q_offset"))
def aqua_prefill_attention(q_sel: jax.Array, khat_blocks: jax.Array,
                           v: jax.Array, block_idx: jax.Array,
                           lengths: jax.Array,
                           kc_part: Optional[jax.Array] = None,
                           *, block_dims: int = 8,
                           q_blk: int = 128, k_blk: int = 128,
                           causal: bool = True,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           q_offset: int = 0) -> jax.Array:
    """Block-sparse AQUA chunked-prefill attention.

    q_sel:       (B, H, NQC, NB_sel, q_blk, bd) — queries, pre-gathered
                 selected dim-blocks per causal query chunk
    khat_blocks: (B, KV, NB_total, bd, S) — dim-major projected key cache
    v:           (B, KV, S, Dv)
    block_idx:   (B, H, NQC, NB_sel) int32 — selected dim-block ids (sorted)
    lengths:     (B,) int32 — valid sequence length per row (keys beyond are
                 masked; query rows beyond produce don't-care output)
    kc_part:     (B, NQC, KT) int32 — per-q-tile *participating* key-chunk
                 indices (sorted ascending, diagonal pinned —
                 ``core.selection.chunk_participating_tiles``), or None to
                 visit every key chunk. When given, the grid's key-chunk
                 extent shrinks from NKC to KT; dropped tiles' key/value
                 bytes are never streamed (hierarchical AQUA's q-tile
                 token-sparsity stage for chunked long prefills).
    scale:       score scale; default 1/sqrt(NB_total * bd). AQUA
                 approximates *full* head-dim scores, so pass
                 1/sqrt(head_dim) when k̂ is statically sliced.
    q_offset:    static row offset of the queries within the key stripe —
                 the chunk-resumable entry (``ops.aqua_prefill_chunk``):
                 the queries are sequence rows [q_offset, q_offset + T)
                 while the keys span [0, S). Masked-out key tiles are
                 exact no-ops in the online softmax, so a q_blk-aligned
                 chunk invocation is bitwise identical to the matching
                 tiles of the monolithic call. 0 = classic full prefill.
    returns out: (B, H, T, Dv) with T = NQC * q_blk
    """
    b, h, nqc, nb_sel, qb, bd = q_sel.shape
    _, kvh, nb_total, bd2, s = khat_blocks.shape
    assert bd == bd2 == block_dims and qb == q_blk
    dv = v.shape[-1]
    g = h // kvh
    assert s % k_blk == 0, (s, k_blk)
    assert q_offset >= 0 and q_offset + nqc * q_blk <= s, \
        (q_offset, nqc, q_blk, s)
    nkc = s // k_blk
    if scale is None:
        scale = 1.0 / ((nb_total * bd) ** 0.5)
    interpret = _rtf.resolve_interpret(interpret)

    hier = kc_part is not None
    kt = kc_part.shape[2] if hier else nkc
    grid = (b, h, nqc, kt, nb_sel)

    if hier:
        # key-chunk axis walks participating tiles only: grid step kci ->
        # logical chunk kc_part[bi, qi, kci] (scalar-prefetch operand 1).
        def q_map(bi, hi, qi, ki, ji, *refs):
            return (bi, hi, qi, ji, 0, 0)

        def k_map(bi, hi, qi, ki, ji, *refs):
            return (bi, hi // g, refs[0][bi, hi, qi, ji], 0,
                    refs[1][bi, qi, ki])

        def v_map(bi, hi, qi, ki, ji, *refs):
            return (bi, hi // g, refs[1][bi, qi, ki], 0)

        def o_map(bi, hi, qi, ki, ji, *refs):
            return (bi, hi, qi, 0)

        nsp = 3
        kernel = functools.partial(_part_kernel, scale=scale, q_blk=q_blk,
                                   k_blk=k_blk, nb_sel=nb_sel, kt=kt,
                                   causal=causal, window=window,
                                   q_offset=q_offset)
        prefetch = (block_idx, kc_part, lengths)
    else:
        def q_map(bi, hi, qi, ki, ji, idx_ref, len_ref):
            return (bi, hi, qi, ji, 0, 0)

        def k_map(bi, hi, qi, ki, ji, idx_ref, len_ref):
            return (bi, hi // g, idx_ref[bi, hi, qi, ji], 0, ki)

        def v_map(bi, hi, qi, ki, ji, idx_ref, len_ref):
            return (bi, hi // g, ki, 0)

        def o_map(bi, hi, qi, ki, ji, idx_ref, len_ref):
            return (bi, hi, qi, 0)

        nsp = 2
        kernel = functools.partial(_kernel, scale=scale, q_blk=q_blk,
                                   k_blk=k_blk, nb_sel=nb_sel, nkc=nkc,
                                   causal=causal, window=window,
                                   q_offset=q_offset)
        prefetch = (block_idx, lengths)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=nsp,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, 1, q_blk, bd), q_map),
            pl.BlockSpec((1, 1, 1, bd, k_blk), k_map),
            pl.BlockSpec((1, 1, k_blk, dv), v_map),
        ],
        out_specs=pl.BlockSpec((1, 1, q_blk, dv), o_map),
        scratch_shapes=[
            pltpu.VMEM((q_blk, k_blk), jnp.float32),  # score accumulator
            pltpu.VMEM((q_blk, 1), jnp.float32),      # running max
            pltpu.VMEM((q_blk, 1), jnp.float32),      # running denom
            pltpu.VMEM((q_blk, dv), jnp.float32),     # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, nqc * q_blk, dv), v.dtype),
        interpret=interpret,
        name="aqua_prefill_attention",
    )(*prefetch, q_sel, khat_blocks, v)
