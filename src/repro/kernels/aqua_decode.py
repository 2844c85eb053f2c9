"""AQUA block-sparse decode-attention Pallas TPU kernel.

TPU-native realization of the paper's magnitude-pruned score computation
(DESIGN.md §2): the projected key cache is stored **dim-major**
(B, KV, NB_total, bd, S) — dim-blocks of ``bd`` sublanes × a long lane-dim
sequence stripe. Per query head, only the ``NB_sel`` dim-blocks selected by
query magnitude are DMA'd HBM→VMEM, via ``PrefetchScalarGridSpec``: the
selected block indices are scalar-prefetched and dereferenced inside the
K BlockSpec ``index_map``. HBM score-read traffic drops to
``NB_sel / NB_total = k_ratio`` of baseline — the decode roofline is
memory-bound, so this is the term the paper's technique buys down on TPU.

The value product and online softmax are fused flash-decode style, so the
(B, H, S) score matrix never materializes in HBM.

Grid: (B, H, num_seq_blocks, NB_sel)  — dim-block index j innermost; the
V block index_map is constant in j, so Pallas keeps the V tile resident
across the j loop (single fetch per seq block). Inside the call the query
and output carry a singleton row axis — (B, H, NB_sel, 1, bd) and
(B, H, 1, Dv) — so each block's last two dims equal the array's, as the
TPU's (8, 128) tiling rule requires.

Mesh-native serving runs this kernel *inside* ``shard_map``
(``repro.core.attention.shard_mapped_decode_kernel``): B and H are then
shard-local lane/head extents while the slot axis S stays whole per
shard — the engine's kernel-native cache layout never slot-shards or
dim-splits the K̂ stripes, so the scalar-prefetched block-index tables
and the ``NB_sel``/``NB_total`` accounting are purely shard-local.

Paged pools take :func:`aqua_paged_decode_attention`, which reads whole
seq-major pages instead: one grid step per (lane, KV head, run of pages)
with all G query heads of the KV head together, copying only the pages
that hold tokens; q arrives with its unselected dim-blocks zeroed. It
runs shard_mapped (``shard_mapped_paged_decode_kernel``): the page lists
it scalar-prefetches are the shard's own lane group's — tables partition
with their lanes over the data axes — while the page pool arrives with
its page axis whole per data shard (pages are lane-global; ``model``
only partitions the pool's KV-head axis, so whole pages ride with each
head). Page ids are pool-global and valid unchanged on every shard, so
the page dereference needs no translation and no collective.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Tokens of whole pages the paged body reads per grid step (a run), and
# computes per online-softmax update (a chunk).
RUN_TOKENS = 2048
CHUNK_TOKENS = 512


def _kernel(idx_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            s_ref, m_ref, l_ref, acc_ref, *, scale: float, seq_blk: int,
            nb_sel: int, nsb: int):
    """Add one selected dim-block's partial scores, (1, bd) @ (bd, S_blk),
    to the sequence block's score tile; after the last dim-block, take an
    online-softmax step over the block. The running max and denominator
    live in (1, 1) VMEM tiles and are read and written whole (``[...]``):
    Mosaic has no scalar VMEM store."""
    b = pl.program_id(0)
    sb = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when((sb == 0) & (j == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j == 0)
    def _reset_scores():
        s_ref[...] = jnp.zeros_like(s_ref)

    q_blk = q_ref[0, 0, 0].astype(jnp.float32)       # (1, bd)
    k_blk = k_ref[0, 0, 0].astype(jnp.float32)       # (bd, S_blk)
    s_ref[...] += jax.lax.dot_general(
        q_blk, k_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == nb_sel - 1)
    def _finalize_block():
        pos = sb * seq_blk + jax.lax.broadcasted_iota(jnp.int32, (1, seq_blk),
                                                      1)
        s = jnp.where(pos < len_ref[b], s_ref[...] * scale, NEG_INF)
        m_prev = m_ref[...]                           # (1, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                        # (1, S_blk)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when(sb == nsb - 1)
        def _write():
            o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                          )[None, None].astype(o_ref.dtype)


def _grouped_paged_kernel(ids_ref, npg_ref, tail_ref, *refs, scale: float,
                          pps: int, chunk: int, ne: int, nr: int, sh: int,
                          cdt):
    """One grid step per (lane, KV head, run of ``pps`` pages): all G query
    heads of the KV head attend over the run's whole K̂/V pages together.

    ``ids_ref`` (B·NE,) lists each lane's physical pages in attention order,
    ``npg_ref`` (B,) how many of them the lane reads and ``tail_ref`` (B,)
    how many slots of its last one hold tokens; every earlier page is full.
    Only those pages are copied HBM→VMEM, one async copy per page into a
    double buffer: step t copies the next grid step's run into slot
    (t+1) % 2 before it computes its own run from slot t % 2, so the grid
    runs in order ("arbitrary"). A run past the lane's length copies and
    computes nothing. Full pages are computed ``chunk`` at a time. q
    arrives with its unselected dim-blocks zeroed, so a whole page's
    (G, D)·(D, ps) product is the selected-dims score. int8
    pools fold their per-page scales (flattened (P·SH,), scalar-prefetched)
    into the score scale and p."""
    quant = sh > 0
    if quant:
        ks_ref, vs_ref, *refs = refs
    (q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
     m_ref, l_ref, acc_ref) = refs
    b, h, r = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nkv = pl.num_programs(1)
    step = (b * nkv + h) * nr + r
    slot = step % 2

    def run_len(bi, ri):
        return jnp.clip(npg_ref[bi] - ri * pps, 0, pps)

    def copies(bi, hi, ri, sl, i):
        page = ids_ref[bi * ne + ri * pps + i]
        return (pltpu.make_async_copy(k_hbm.at[page, hi], kbuf.at[sl, i],
                                      sem.at[0, sl]),
                pltpu.make_async_copy(v_hbm.at[page, hi], vbuf.at[sl, i],
                                      sem.at[1, sl]))

    def start_run(bi, hi, ri, sl):
        def body(i, carry):
            for c in copies(bi, hi, ri, sl, i):
                c.start()
            return carry
        jax.lax.fori_loop(0, run_len(bi, ri), body, 0)

    @pl.when(step == 0)
    def _first():
        start_run(b, h, r, slot)

    nh = jnp.where(r == nr - 1, h + 1, h)
    nbi = jnp.where(nh == nkv, b + 1, b)

    @pl.when(nbi < pl.num_programs(0))
    def _prefetch_next():
        start_run(nbi, jnp.where(nh == nkv, 0, nh),
                  jnp.where(r == nr - 1, 0, r + 1), 1 - slot)

    @pl.when(r == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(cdt)                        # (G, D)
    _, _, ps, d = kbuf.shape
    dv = vbuf.shape[-1]

    def per_token(ref, pages):
        """(1, len(pages)·ps) row of each page's scale from ``ref``."""
        return jnp.concatenate([jnp.full((1, ps), ref[pg], jnp.float32)
                                for pg in pages], axis=1)

    def attend(i0, c, tail=None):
        """Online-softmax update over the run's pages i0 .. i0+c-1 at once:
        (G, D)·(D, c·ps) scores, then (G, c·ps)·(c·ps, Dv) values."""
        pages = [copies(b, h, r, slot, i0 + j) for j in range(c)]
        for kc, _ in pages:
            kc.wait()
        k = kbuf[slot, pl.ds(i0, c)].reshape(c * ps, d).astype(cdt)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quant:
            sidx = [ids_ref[b * ne + r * pps + i0 + j] * sh
                    + (h if sh > 1 else 0) for j in range(c)]
            s = s * per_token(ks_ref, sidx)
        if tail is not None:
            s = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
                          < tail, s, NEG_INF)
        m_prev = m_ref[...]                           # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        for _, vc in pages:
            vc.wait()
        v = vbuf[slot, pl.ds(i0, c)].reshape(c * ps, dv).astype(jnp.float32)
        if tail is not None:
            v = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0)
                          < tail, v, 0.0)
        if quant:
            p = p * per_token(vs_ref, sidx)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # whole chunks of full pages, then single full pages; the run holding
    # the lane's last page masks that page's tail slots
    left = npg_ref[b] - r * pps
    last = (left > 0) & (left <= pps)
    n = run_len(b, r)
    n_full = jnp.where(last, n - 1, n)
    n_chunks = n_full // chunk

    def chunk_body(j, carry):
        attend(j * chunk, chunk)
        return carry

    def page_body(i, carry):
        attend(i, 1)
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk_body, 0)
    jax.lax.fori_loop(n_chunks * chunk, n_full, page_body, 0)

    @pl.when(last)
    def _tail():
        attend(n - 1, 1, tail_ref[b])

    @pl.when(r == nr - 1)
    def _write():
        o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("pages_per_step", "scale",
                                             "interpret"))
def aqua_paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                                v_pool: jax.Array, page_ids: jax.Array,
                                num_pages: jax.Array, tails: jax.Array,
                                k_scale=None, v_scale=None, *,
                                pages_per_step: int, scale: float,
                                interpret=None) -> jax.Array:
    """AQUA decode attention over a paged K̂/V pool, all G query heads of a
    KV head per grid step (:func:`_grouped_paged_kernel`).

    q:          (B, H, D) projected query with each head's unselected
                dim-blocks zeroed (the AQUA selection, applied as a mask)
    k_pool:     (P, KV, ps, D) projected key pool, seq-major per page
    v_pool:     (P, KV, ps, Dv)
    page_ids:   (B, NE) int32 — physical page of each attended entry, in
                order; entries at or past ``num_pages[b]`` are never read
    num_pages:  (B,) int32 — entries each lane attends
    tails:      (B,) int32 — token slots in use in the lane's last entry
                (``ps`` when it is full); every earlier entry is full
    k_scale, v_scale: (P, SH) f32 per-page scales of int8 pools (SH ∈
                {KV, 1}), or None
    returns:    (B, H, Dv) float32

    Grid (B, KV, ceil(NE / pages_per_step)). Each K̂/V page is read once
    per KV head. Scores and the value product accumulate in f32; the score
    product runs in the operands' common dtype (int8 pages upcast to q's;
    f32 holds a bf16 × bf16 product exactly). Under a serving mesh B and
    KV are shard-local and ``page_ids`` holds pool-global ids.
    """
    from repro import runtime_flags as _rtf
    b, h, d = q.shape
    _, kvh, ps, _ = k_pool.shape
    dv = v_pool.shape[-1]
    g = h // kvh
    ne = page_ids.shape[1]
    pps = pages_per_step
    nr = pl.cdiv(ne, pps)
    quant = k_scale is not None
    cdt = q.dtype if quant else jnp.promote_types(q.dtype, k_pool.dtype)
    sh = k_scale.shape[1] if quant else 0
    scalars = [page_ids.reshape(-1), num_pages, tails]
    if quant:
        scalars += [k_scale.astype(jnp.float32).reshape(-1),
                    v_scale.astype(jnp.float32).reshape(-1)]

    def head_map(bi, hi, ri, *refs):
        return (bi, hi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, kvh, nr),
        in_specs=[pl.BlockSpec((None, g, None, d), head_map),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, g, None, dv), head_map),
        scratch_shapes=[
            pltpu.VMEM((2, pps, ps, d), k_pool.dtype),
            pltpu.VMEM((2, pps, ps, dv), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),          # (K/V, slot)
            pltpu.VMEM((g, 1), jnp.float32),          # running max
            pltpu.VMEM((g, 1), jnp.float32),          # running denom
            pltpu.VMEM((g, dv), jnp.float32),         # output accumulator
        ],
    )
    kernel = functools.partial(_grouped_paged_kernel, scale=scale, pps=pps,
                               chunk=max(1, min(pps, CHUNK_TOKENS // ps)),
                               ne=ne, nr=nr, sh=sh, cdt=cdt)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=_rtf.resolve_interpret(interpret),
        name="aqua_paged_decode_attention",
    )(*scalars, q.astype(jnp.float32)[:, :, None], k_pool, v_pool)[:, :, 0]


@functools.partial(jax.jit, static_argnames=("block_dims", "seq_blk",
                                             "scale", "interpret"))
def aqua_decode_attention(q_sel: jax.Array, khat_blocks: jax.Array,
                          v: jax.Array, block_idx: jax.Array,
                          lengths: jax.Array, *, block_dims: int = 8,
                          seq_blk: int = 128, scale=None,
                          interpret=None) -> jax.Array:
    """Block-sparse AQUA decode attention.

    q_sel:       (B, H, NB_sel, bd)  — query, pre-gathered selected blocks
    khat_blocks: (B, KV, NB_total, bd, S) — dim-major projected key cache
    v:           (B, KV, S, Dv)
    block_idx:   (B, H, NB_sel) int32 — selected dim-block ids (sorted)
    lengths:     (B,) int32 — valid cache length per row
    scale:       score scale; defaults to 1/sqrt(NB_total * bd). Pass
                 1/sqrt(head_dim) when k̂ is statically sliced (AQUA-Memory)
                 — the paper approximates *full* head-dim scores.
    interpret:   None -> resolved by runtime_flags (compiled iff on TPU)
    returns out: (B, H, Dv)
    """
    from repro import runtime_flags as _rtf
    b, h, nb_sel, bd = q_sel.shape
    _, kvh, nb_total, bd2, s = khat_blocks.shape
    assert bd == bd2 == block_dims
    dv = v.shape[-1]
    g = h // kvh
    assert s % seq_blk == 0, (s, seq_blk)
    nsb = s // seq_blk
    if scale is None:
        # scale by the FULL head-dim sqrt of the projected cache.
        scale = 1.0 / ((nb_total * bd) ** 0.5)
    interpret = _rtf.resolve_interpret(interpret)

    grid = (b, h, nsb, nb_sel)

    def q_map(bi, hi, sbi, ji, idx_ref, len_ref):
        return (bi, hi, ji, 0, 0)

    def k_map(bi, hi, sbi, ji, idx_ref, len_ref):
        return (bi, hi // g, idx_ref[bi, hi, ji], 0, sbi)

    def v_map(bi, hi, sbi, ji, idx_ref, len_ref):
        return (bi, hi // g, sbi, 0)

    def o_map(bi, hi, sbi, ji, idx_ref, len_ref):
        return (bi, hi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, 1, bd), q_map),
            pl.BlockSpec((1, 1, 1, bd, seq_blk), k_map),
            pl.BlockSpec((1, 1, seq_blk, dv), v_map),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, dv), o_map),
        scratch_shapes=[
            pltpu.VMEM((1, seq_blk), jnp.float32),   # score accumulator
            pltpu.VMEM((1, 1), jnp.float32),         # running max
            pltpu.VMEM((1, 1), jnp.float32),         # running denom
            pltpu.VMEM((1, dv), jnp.float32),        # output accumulator
        ],
    )
    kernel = functools.partial(_kernel, scale=scale, seq_blk=seq_blk,
                               nb_sel=nb_sel, nsb=nsb)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, dv), v.dtype),
        interpret=interpret,
        name="aqua_decode_attention",
    )(block_idx, lengths, q_sel[:, :, :, None], khat_blocks, v)[:, :, 0]
