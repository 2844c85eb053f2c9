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

The paged kernel (:func:`aqua_paged_decode_attention`) rides the same
machinery shard_mapped (``shard_mapped_paged_decode_kernel``): the
page-table rows it scalar-prefetches are the shard's own lane group's —
tables partition with their lanes over the data axes — while the page
pool arrives with its page axis whole per data shard (pages are
lane-global; ``model`` only partitions the pool's KV-head axis, so whole
pages and whole dim-blocks ride with each head). Table entries are
pool-global page ids valid unchanged on every shard, so the ``index_map``
page dereference needs no translation and no collective — exactly like
the contiguous kernel's dim-block indices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _accumulate_scores(sb, j, q_ref, k_ref, s_ref, m_ref, l_ref, acc_ref):
    """Shared prologue of every decode body: reset the running softmax
    state at a row's first step, the score tile at each sequence block's
    first dim-block, then add this selected dim-block's partial scores:
    (1, bd) @ (bd, S_blk)."""
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


def _softmax_update(s, valid, v_blk, m_ref, l_ref, acc_ref):
    """Online-softmax step over one sequence block. ``s`` (1, S_blk)
    scaled scores, ``valid`` their position mask, ``v_blk`` (S_blk, Dv).
    The running max and denominator live in (1, 1) VMEM tiles and are
    read and written whole (``[...]``): Mosaic has no scalar VMEM store."""
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[...]                               # (1, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                            # (1, S_blk)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _write_out(o_ref, acc_ref, l_ref):
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                  )[None, None].astype(o_ref.dtype)


def _kernel(idx_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            s_ref, m_ref, l_ref, acc_ref, *, scale: float, seq_blk: int,
            nb_sel: int, nsb: int):
    b = pl.program_id(0)
    sb = pl.program_id(2)
    j = pl.program_id(3)
    _accumulate_scores(sb, j, q_ref, k_ref, s_ref, m_ref, l_ref, acc_ref)

    @pl.when(j == nb_sel - 1)
    def _finalize_block():
        pos = sb * seq_blk + jax.lax.broadcasted_iota(jnp.int32, (1, seq_blk),
                                                      1)
        _softmax_update(s_ref[...] * scale, pos < len_ref[b],
                        v_ref[0, 0].astype(jnp.float32),
                        m_ref, l_ref, acc_ref)

        @pl.when(sb == nsb - 1)
        def _write():
            _write_out(o_ref, acc_ref, l_ref)


def _paged_kernel(idx_ref, pt_ref, len_ref, *rest, **kw):
    """Paged twin of :func:`_kernel`: the kernel body is identical (the
    page table is consumed only by the BlockSpec ``index_map``s), so the
    extra scalar-prefetch ref is simply dropped here."""
    del pt_ref
    _kernel(idx_ref, len_ref, *rest, **kw)


def _paged_part_kernel(idx_ref, pt_ref, part_ref, len_ref,
                       q_ref, k_ref, v_ref, o_ref,
                       s_ref, m_ref, l_ref, acc_ref, *, scale: float,
                       seq_blk: int, nb_sel: int, nsb: int, bpp: int):
    """Hierarchical (two-stage) twin of :func:`_paged_kernel`.

    The grid's sequence-block axis runs over *participating* pages only
    (``nsb = KP * bpp``); ``part_ref`` (B, KP) maps each grid step to its
    logical page so the position validity test stays token-exact. Pages
    the stage-1 ranking dropped are never touched — their HBM bytes are
    simply not streamed (the BlockSpec ``index_map`` never emits them)."""
    b = pl.program_id(0)
    sb = pl.program_id(2)
    j = pl.program_id(3)
    _accumulate_scores(sb, j, q_ref, k_ref, s_ref, m_ref, l_ref, acc_ref)

    @pl.when(j == nb_sel - 1)
    def _finalize_block():
        lp = part_ref[b, sb // bpp]                   # logical page id
        pos = (lp * bpp + sb % bpp) * seq_blk + jax.lax.broadcasted_iota(
            jnp.int32, (1, seq_blk), 1)
        _softmax_update(s_ref[...] * scale, pos < len_ref[b],
                        v_ref[0, 0].astype(jnp.float32),
                        m_ref, l_ref, acc_ref)

        @pl.when(sb == nsb - 1)
        def _write():
            _write_out(o_ref, acc_ref, l_ref)


def _paged_part_quant_kernel(idx_ref, pt_ref, part_ref, len_ref,
                             ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref,
                             s_ref, m_ref, l_ref, acc_ref, *, scale: float,
                             seq_blk: int, nb_sel: int, nsb: int, bpp: int,
                             g: int, s_stride: int):
    """Hierarchical int8 variant: :func:`_paged_part_kernel`'s logical-page
    remap composed with :func:`_paged_quant_kernel`'s scale folding — the
    per-page scales are looked up through the participating page's table
    entry, positions through its logical index."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    sb = pl.program_id(2)
    j = pl.program_id(3)
    _accumulate_scores(sb, j, q_ref, k_ref, s_ref, m_ref, l_ref, acc_ref)

    @pl.when(j == nb_sel - 1)
    def _finalize_block():
        lp = part_ref[b, sb // bpp]                   # logical page id
        page = jnp.maximum(pt_ref[b, lp], 0)
        kv = (h // g) * s_stride
        pos = (lp * bpp + sb % bpp) * seq_blk + jax.lax.broadcasted_iota(
            jnp.int32, (1, seq_blk), 1)
        v_blk = v_ref[0, 0].astype(jnp.float32) * vs_ref[page, kv]
        _softmax_update(s_ref[...] * (scale * ks_ref[page, kv]),
                        pos < len_ref[b], v_blk, m_ref, l_ref, acc_ref)

        @pl.when(sb == nsb - 1)
        def _write():
            _write_out(o_ref, acc_ref, l_ref)


def _paged_quant_kernel(idx_ref, pt_ref, len_ref, ks_ref, vs_ref,
                        q_ref, k_ref, v_ref, o_ref,
                        s_ref, m_ref, l_ref, acc_ref, *, scale: float,
                        seq_blk: int, nb_sel: int, nsb: int, bpp: int,
                        g: int, s_stride: int):
    """int8 paged variant: dequant-free score accumulation.

    The int8 K̂ tiles feed the same dot_general (upcast in-register); the
    per-page key scale is *folded into the softmax scale* at finalize —
    every sequence block lives inside exactly one physical page, so one
    scalar multiply replaces a per-element dequant of the K tile. V tiles
    dequantize once per (b, h, sb) with their page's scalar. The scales
    ride scalar prefetch (SMEM) like the page table; ``s_stride`` is 1
    for per-(page, head) scales and 0 for one-scale-per-page."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    sb = pl.program_id(2)
    j = pl.program_id(3)
    _accumulate_scores(sb, j, q_ref, k_ref, s_ref, m_ref, l_ref, acc_ref)

    @pl.when(j == nb_sel - 1)
    def _finalize_block():
        page = jnp.maximum(pt_ref[b, sb // bpp], 0)
        kv = (h // g) * s_stride
        pos = sb * seq_blk + jax.lax.broadcasted_iota(jnp.int32, (1, seq_blk),
                                                      1)
        v_blk = v_ref[0, 0].astype(jnp.float32) * vs_ref[page, kv]
        _softmax_update(s_ref[...] * (scale * ks_ref[page, kv]),
                        pos < len_ref[b], v_blk, m_ref, l_ref, acc_ref)

        @pl.when(sb == nsb - 1)
        def _write():
            _write_out(o_ref, acc_ref, l_ref)


@functools.partial(jax.jit, static_argnames=("block_dims", "seq_blk",
                                             "scale", "interpret"))
def aqua_paged_decode_attention(q_sel: jax.Array, khat_pages: jax.Array,
                                v_pages: jax.Array, block_idx: jax.Array,
                                page_table: jax.Array, lengths: jax.Array,
                                k_scale=None, v_scale=None, part_idx=None,
                                *, block_dims: int = 8, seq_blk: int = 128,
                                scale=None, interpret=None) -> jax.Array:
    """Block-sparse AQUA decode attention over a *paged* K/V pool.

    q_sel:       (B, H, NB_sel, bd)  — query, pre-gathered selected blocks
    khat_pages:  (P, KV, NB_total, bd, ps) — dim-major projected key pool
                 (page-major: each physical page holds a ``ps``-token
                 dim-major stripe)
    v_pages:     (P, KV, ps, Dv)
    block_idx:   (B, H, NB_sel) int32 — selected dim-block ids (sorted)
    page_table:  (B, NP_lane) int32 — physical page of each logical page,
                 -1 unmapped (clamped; masked off via ``lengths``)
    lengths:     (B,) int32 — valid cache length per row. Full-cache
                 policy only: logical slot == token position.
    k_scale, v_scale: (P, SH) f32 per-page scales for int8 pools (SH ∈
                 {KV, 1}); both None for full-precision pools.
    part_idx:    (B, KP) int32 — stage-1 *participating* logical page
                 indices per lane, sorted ascending
                 (``core.selection.participating_pages``), or None to
                 attend every page. Entries must be valid logical indices
                 in [0, NP_lane); pages past the lane's length contribute
                 nothing (position masking). When given, the grid's
                 sequence-block extent shrinks from NP_lane to KP — the
                 dropped pages' K̂/V tiles are never streamed from HBM.
    returns out: (B, H, Dv)

    The page table is the second scalar-prefetch operand: the K and V
    ``index_map``s dereference it to locate the physical page of each
    sequence block — the same scalar-prefetch indirection the dim-block
    selection already uses, composed on the sequence axis. HBM traffic is
    unchanged vs the contiguous kernel (pages only redirect addressing);
    the pool itself is what shrinks (repro.core.kvcache.PagedAttnCache).

    Quantized pools compose on the same machinery: the per-page scales
    are scalar-prefetch operands 4/5, the int8 K̂ tile feeds the MXU
    upcast in-register, and the key scale folds into the softmax scale at
    finalize — no dequantized K/V page ever materializes
    (:func:`_paged_quant_kernel`). HBM score-read traffic drops a further
    4× vs bf16 pools (1 byte/elem), compounding with the ``k_ratio``
    dim-sparsity term.

    Shard-local contract: under a serving mesh this runs inside
    ``shard_map`` with B the shard's lane-group extent and ``page_table``
    that group's rows, while ``khat_pages``/``v_pages`` keep their page
    axis whole (P is pool-global; only KV is shard-local, over ``model``).
    The entries of ``page_table`` are pool-global page ids, so the
    ``index_map`` dereference above is valid verbatim on every shard.
    """
    from repro import runtime_flags as _rtf
    b, h, nb_sel, bd = q_sel.shape
    _, kvh, nb_total, bd2, ps = khat_pages.shape
    assert bd == bd2 == block_dims
    npl = page_table.shape[1]
    dv = v_pages.shape[-1]
    g = h // kvh
    assert ps % seq_blk == 0, (ps, seq_blk)
    bpp = ps // seq_blk                       # sequence blocks per page
    hier = part_idx is not None
    nsb = (part_idx.shape[1] if hier else npl) * bpp
    if scale is None:
        scale = 1.0 / ((nb_total * bd) ** 0.5)
    interpret = _rtf.resolve_interpret(interpret)

    grid = (b, h, nsb, nb_sel)
    quant = k_scale is not None
    q_rows = q_sel[:, :, :, None]             # (B, H, NB_sel, 1, bd)
    nsp = (3 if not quant else 5) + (1 if hier else 0)

    # trailing scalar-prefetch refs: (idx, pt[, part], len[, ks, vs]) —
    # the maps only dereference idx/pt/part, so *refs covers all arities.
    def q_map(bi, hi, sbi, ji, *refs):
        return (bi, hi, ji, 0, 0)

    if hier:
        # sequence-block axis walks participating pages only: grid step
        # sbi -> logical page part[bi, sbi // bpp] -> physical page.
        def k_map(bi, hi, sbi, ji, *refs):
            lp = refs[2][bi, sbi // bpp]
            page = jnp.maximum(refs[1][bi, lp], 0)
            return (page, hi // g, refs[0][bi, hi, ji], 0, sbi % bpp)

        def v_map(bi, hi, sbi, ji, *refs):
            lp = refs[2][bi, sbi // bpp]
            page = jnp.maximum(refs[1][bi, lp], 0)
            return (page, hi // g, sbi % bpp, 0)
    else:
        def k_map(bi, hi, sbi, ji, *refs):
            page = jnp.maximum(refs[1][bi, sbi // bpp], 0)
            return (page, hi // g, refs[0][bi, hi, ji], 0, sbi % bpp)

        def v_map(bi, hi, sbi, ji, *refs):
            page = jnp.maximum(refs[1][bi, sbi // bpp], 0)
            return (page, hi // g, sbi % bpp, 0)

    def o_map(bi, hi, sbi, ji, *refs):
        return (bi, hi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=nsp,
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
    if quant:
        common = dict(scale=scale, seq_blk=seq_blk, nb_sel=nb_sel, nsb=nsb,
                      bpp=bpp, g=g,
                      s_stride=1 if k_scale.shape[1] > 1 else 0)
        # int8 pools can't carry the output dtype; accumulate/emit f32.
        out_dtype = jnp.float32
        scales = (k_scale.astype(jnp.float32), v_scale.astype(jnp.float32))
        if hier:
            kernel = functools.partial(_paged_part_quant_kernel, **common)
            operands = (block_idx, page_table, part_idx, lengths, *scales,
                        q_rows, khat_pages, v_pages)
        else:
            kernel = functools.partial(_paged_quant_kernel, **common)
            operands = (block_idx, page_table, lengths, *scales,
                        q_rows, khat_pages, v_pages)
    else:
        out_dtype = v_pages.dtype
        if hier:
            kernel = functools.partial(_paged_part_kernel, scale=scale,
                                       seq_blk=seq_blk, nb_sel=nb_sel,
                                       nsb=nsb, bpp=bpp)
            operands = (block_idx, page_table, part_idx, lengths, q_rows,
                        khat_pages, v_pages)
        else:
            kernel = functools.partial(_paged_kernel, scale=scale,
                                       seq_blk=seq_blk, nb_sel=nb_sel,
                                       nsb=nsb)
            operands = (block_idx, page_table, lengths, q_rows, khat_pages,
                        v_pages)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, dv), out_dtype),
        interpret=interpret,
        name="aqua_paged_decode_attention",
    )(*operands)[:, :, 0]


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
