"""Unified multi-head attention with first-class AQUA support.

Covers: MHA / GQA / MQA, full + sliding-window/local masks, RoPE, qk-norm,
QKV bias, AQUA projection + magnitude selection, AQUA-Memory static slice,
and H2O heavy-hitter eviction — for both prefill (sequence) and decode
(single-step with slot cache) modes.

Backend registry contract
-------------------------
The core attention product is dispatched through a string-keyed registry
(:data:`_BACKENDS`); ``AttentionConfig.backend`` selects the entry and
:func:`resolve_backend` applies the fallback policy. A backend's
``prefill`` callable receives model-layout tensors

  q (B, S, KV, G, Dq), k (B, S, KV, Dq), v (B, S, KV, Dv)

and returns ``(out (B, S, KV, G, Dv), weights | None)``. Non-AQUA
backends and ``aqua-masked-dense`` get the magnitude-*masked* query
(masked-q identity, DESIGN.md §2); ``aqua-block-sparse`` gets the
unmasked projected q̂/k̂ and performs chunk-level dim-block selection
inside the kernel wrapper. ``decode`` (optional) receives the projected
query (B, KV, G, Dq) plus the slot cache and returns (B, KV, G, Dv).

Built-in backends: ``dense-jnp`` (materialized scores, auto-switching to
the chunked online-softmax scan for long sequences), ``flash`` (Pallas
flash kernel), ``aqua-masked-dense`` (jnp reference for AQUA),
``aqua-block-sparse`` (Pallas chunked-prefill + decode kernels streaming
only the selected dim-blocks). ``auto`` resolves to kernels on TPU and
jnp references elsewhere; off-TPU, kernel backends fall back to the
masked-dense reference when Pallas is unavailable
(``runtime_flags.PALLAS_OVERRIDE``).

Conventions:
  x            (B, S, d_model)
  q            (B, S, KV, G, D)   G = group size (H = KV*G)
  k, v         (B, S, KV, D)
  proj P       (KV, D, D)         per-layer, per-GQA-group (paper §6.3)
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import logging
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from repro import runtime_flags as _rtf

logger = logging.getLogger(__name__)


def _scan(*args, **kw):
    kw.update(_rtf.scan_kwargs())
    return jax.lax.scan(*args, **kw)


from repro.configs.base import AquaConfig, AttentionConfig
from repro.core import aqua as aqua_lib
from repro.core.aqua import ceil_to as _ceil_to
from repro.core import kvcache as kv
# single-source fallback-reason vocabulary: the dedup sink keys off these
# exact strings and DispatchPlan.reasons carries the same constants, so
# the plan's prediction and the trace-time warnings can never drift apart
from repro.core.dispatch import (REASON_NONDIVISIBLE_MESH,
                                 REASON_PAGE_GEOMETRY,
                                 REASON_QUANT_RESIDENCY)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over the last axis. x: (..., S, ..., D) with
    positions broadcastable to x's sequence axis; here we require
    x: (B, S, *, D) and positions: (B, S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    # broadcast over head axes between S and D
    extra = x.ndim - 3  # number of axes between S and D
    for _ in range(extra):
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:2 * half]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    rest = x[..., 2 * half:]  # odd head dims (e.g. danube D=80 is even; safe)
    return jnp.concatenate([out1.astype(x.dtype), out2.astype(x.dtype), rest],
                           axis=-1)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Parameter init / QKV projection
# ---------------------------------------------------------------------------


def init_attention_params(rng: jax.Array, d_model: int, cfg: AttentionConfig,
                          dtype=jnp.float32) -> dict:
    h, g, d = cfg.num_kv_heads, cfg.group_size, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    std = d_model ** -0.5
    p = {
        "wq": jax.random.normal(k1, (d_model, h, g, d), dtype) * std,
        "wk": jax.random.normal(k2, (d_model, h, d), dtype) * std,
        "wv": jax.random.normal(k3, (d_model, h, d), dtype) * std,
        "wo": jax.random.normal(k4, (h, g, d, d_model), dtype) * std,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, g, d), dtype)
        p["bk"] = jnp.zeros((h, d), dtype)
        p["bv"] = jnp.zeros((h, d), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((d,), dtype)
        p["k_norm"] = jnp.ones((d,), dtype)
    return p


def qkv(params: dict, x: jax.Array, cfg: AttentionConfig,
        positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns q (B,S,KV,G,D), k (B,S,KV,D), v (B,S,KV,D), RoPE'd."""
    q = jnp.einsum("bsm,mkgd->bskgd", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsm,mkd->bskd", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsm,mkd->bskd", x, params["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# AQUA projection helpers
# ---------------------------------------------------------------------------


@jax.named_scope("aqua.project")
def project_q(q: jax.Array, proj: Optional[jax.Array]) -> jax.Array:
    if proj is None:
        return q
    return jnp.einsum("bskgd,kde->bskge", q, proj.astype(q.dtype))


@jax.named_scope("aqua.project")
def project_k(k: jax.Array, proj: Optional[jax.Array]) -> jax.Array:
    if proj is None:
        return k
    return jnp.einsum("bskd,kde->bske", k, proj.astype(k.dtype))


def _aqua_project(q, k, aqua: Optional[AquaConfig], proj, head_dim: int):
    """Project + statically slice q̂ and k̂ per AQUA config (no mask — the
    magnitude mask is only materialized for the masked-dense backends; the
    block-sparse kernels do their own selection)."""
    if aqua is None or not aqua.enabled:
        return q, k
    qh = project_q(q, proj)
    kh = project_k(k, proj)
    kept = aqua.kept_dims(head_dim)
    return qh[..., :kept], kh[..., :kept]


@jax.named_scope("aqua.select")
def _aqua_mask(qh, aqua: AquaConfig, head_dim: int):
    return aqua_lib.magnitude_mask(qh, aqua.topk_dims(head_dim),
                                   block_dims=aqua.block_dims)


@jax.named_scope("aqua.select")
def _chunk_tile_mask(qh, aqua: AquaConfig, q_blk: int,
                     lengths: Optional[jax.Array]):
    """Per-*tile* dim-block mask reproducing the block-sparse kernel's
    chunk-aggregated selection (``aqua.chunk_topk_block_indices``) on the
    reference layout: all ``q_blk`` queries of a tile share the block set
    their summed |q̂| picks. The chunked-prefill serve path uses this so a
    chunk's selection equals the monolithic kernel invocation's for tiles
    at the same anchor (the engine keeps chunk cursors q_blk-aligned —
    ``REASON_CHUNK_GEOMETRY`` gates geometries where it can't).

    qh: (B, T, KV, G, D) projected (sliced) queries; lengths: (B,) valid
    rows (padding is excluded from the aggregation, as in the kernel
    wrapper). Returns a 0/1 mask shaped like ``qh``.
    """
    from repro.kernels.ops import round_k_dims
    b, t, kvh, g, d = qh.shape
    bd = aqua.block_dims
    if lengths is None:
        lengths = jnp.full((b,), t, jnp.int32)
    qf = qh.transpose(0, 2, 3, 1, 4).reshape(b, kvh * g, t, d)
    tpad = _ceil_to(t, q_blk)
    if tpad != t:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, tpad - t), (0, 0)))
    k_dims = round_k_dims(d, aqua.k_ratio, bd)
    bidx = aqua_lib.chunk_topk_block_indices(qf, k_dims, bd, q_blk, lengths)
    nb = d // bd
    bmask = jnp.zeros((b, kvh * g, tpad // q_blk, nb), qh.dtype)
    bmask = jnp.put_along_axis(bmask, bidx, 1.0, axis=-1, inplace=False)
    mask = jnp.repeat(bmask, bd, axis=-1)                 # (B, H, NQC, D)
    mask = jnp.repeat(mask[:, :, :, None, :], q_blk, axis=3)
    mask = mask.reshape(b, kvh * g, tpad, d)[:, :, :t]
    return mask.reshape(b, kvh, g, t, d).transpose(0, 3, 1, 2, 4)


# ---------------------------------------------------------------------------
# Mesh-native attention: shard_map-wrapped cores for every backend.
#
# Per-(batch, kv-head) attention is embarrassingly parallel — the softmax
# runs over the slot/sequence axis, which every shard holds in full — so
# under a (data × model) serving mesh both the masked-dense jnp cores and
# the Pallas block-sparse kernels partition lanes over the data axes and
# KV heads over the model axis with *zero* collectives inside the wrapped
# region. Wrapping in shard_map (instead of leaving GSPMD to infer — or,
# for Pallas, silently all-gather at the opaque kernel boundary) pins
# that layout: the KV cache never gathers, the scalar-prefetched
# block-index tables are computed per shard, and the only model-axis
# communication in a step is the reduce for the output projection,
# outside the core.
#
# The mesh is installed around *trace time* by the serving engine
# (``use_decode_mesh``); compiled executables bake it in, so concurrent
# single-device engines in the same process are unaffected.
# ---------------------------------------------------------------------------

_DECODE_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "aqua_decode_mesh", default=None)
_FALLBACK_SINK: contextvars.ContextVar = contextvars.ContextVar(
    "aqua_mesh_fallback_sink", default=None)


def decode_mesh():
    return _DECODE_MESH.get()


@contextlib.contextmanager
def use_decode_mesh(mesh, fallback_sink=None):
    """Install ``mesh`` as the decode-sharding mesh for calls traced inside
    this context (no-op when ``mesh`` is None).

    Backed by ``contextvars.ContextVar`` rather than module globals:
    nested contexts in one process (engine-in-engine tests, ``--verify``
    solo replays) restore their *own* predecessor value on exit instead of
    whatever a sibling left behind, and concurrent engines on other
    threads / pytest workers never observe each other's mesh.

    ``fallback_sink``: a caller-owned set that receives the
    (backend, mode, reason) key of every mesh-kernel fallback traced in
    this context, and keys the once-per-sink warning dedup — the serving
    engine passes its own set so each engine surfaces and owns its
    fallbacks regardless of what other engines in the process did."""
    t_mesh = _DECODE_MESH.set(mesh)
    t_sink = _FALLBACK_SINK.set(fallback_sink)
    try:
        yield
    finally:
        _FALLBACK_SINK.reset(t_sink)
        _DECODE_MESH.reset(t_mesh)


# Hierarchical token sparsity rides the same trace-time installation
# pattern as the mesh: the engine resolves ``SparsitySpec.kept_pages``
# once at construction and installs (kept_pages, pin_recent_pages) around
# its jitted calls; the paged decode product picks it up and builds the
# step's ``SelectionPlan``. Baked into compiled executables like the
# mesh, so concurrent engines with different ratios coexist.
_TOKEN_SPARSITY: contextvars.ContextVar = contextvars.ContextVar(
    "aqua_token_sparsity", default=None)


def token_sparsity():
    """The installed (kept_pages, pin_recent_pages) tuple, or None."""
    return _TOKEN_SPARSITY.get()


@contextlib.contextmanager
def use_token_sparsity(kept_pages, pin_recent_pages=2):
    """Install stage-1 page participation for calls traced inside this
    context (no-op when ``kept_pages`` is None — every page participates).
    ``kept_pages`` is the per-lane participating-page count
    (``SparsitySpec.kept_pages(pages_per_lane)``)."""
    tok = _TOKEN_SPARSITY.set(
        None if kept_pages is None else (int(kept_pages),
                                         int(pin_recent_pages)))
    try:
        yield
    finally:
        _TOKEN_SPARSITY.reset(tok)


# Process-wide aggregate of mesh-fallback events (in addition to any
# per-engine sink), explicitly resettable by test fixtures so warning
# assertions don't depend on suite execution order (the previous
# ``functools.lru_cache`` dedup made them order-dependent). Warning
# *emission* dedups per sink — i.e. per engine — when one is installed.
_MESH_FALLBACK_WARNED: set = set()


def reset_mesh_fallback_warnings() -> None:
    """Clear the process-wide fallback aggregate (test fixtures)."""
    _MESH_FALLBACK_WARNED.clear()


def mesh_fallback_events() -> Tuple[Tuple[str, str, str], ...]:
    """(backend, mode, reason) keys warned process-wide since the last
    reset. Engines expose their own per-engine view
    (``ContinuousBatchingEngine.mesh_fallback_events``) — prefer that for
    asserting a specific engine really served the kernel path."""
    return tuple(sorted(_MESH_FALLBACK_WARNED))


def _log_mesh_kernel_fallback(backend_name: str, mode: str,
                              reason: str = "") -> None:
    key = (backend_name, mode, reason)
    sink = _FALLBACK_SINK.get()
    dedup = _MESH_FALLBACK_WARNED if sink is None else sink
    already = key in dedup
    # the process aggregate records every traced fallback unconditionally —
    # a reset must never be masked by an engine sink that already holds
    # the key (the dedup below only gates warning *emission*)
    _MESH_FALLBACK_WARNED.add(key)
    if already:
        return
    if sink is not None:
        sink.add(key)
    logger.warning(
        "attention backend %r: %s is falling back to the shard_map/jnp "
        "reference path for mesh-native serving%s",
        backend_name, mode, f" ({reason})" if reason else "")


def _masked_dense_decode_core(qq: jax.Array, k: jax.Array, v: jax.Array,
                              positions: jax.Array, count: jax.Array,
                              *, head_dim: int, window: Optional[int]
                              ) -> Tuple[jax.Array, jax.Array]:
    """Reference decode core on cache leaves. qq (B, KV, G, Dk) —
    magnitude-masked when AQUA is on; k (B, KV, S, Dk); v (B, KV, S, Dv);
    positions (B, S); count (B,). Returns (out (B, KV, G, Dv),
    weights (B, KV, G, S) for H2O accumulation)."""
    scores = jnp.einsum("bkgd,bksd->bkgs", qq, k.astype(qq.dtype))
    scores = scores.astype(jnp.float32) / jnp.sqrt(float(head_dim))
    vm = kv.valid_mask_from(positions, count, window=window)
    scores = jnp.where(vm[:, None, None, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", weights.astype(v.dtype), v)
    return out, weights


def _shard_mapped_decode_core(mesh, qq, k, v, positions, count, *,
                              head_dim: int, window: Optional[int]):
    """Run the masked-dense decode core under shard_map on ``mesh``:
    lanes (batch) over the data axes, KV heads over ``model``, softmax
    axis intact per shard. Falls back to the plain core when neither axis
    divides its mesh extent (the specs sanitize to fully-replicated)."""
    from repro.distributed import sharding as dsh

    b, kvh = qq.shape[0], qq.shape[1]
    dp = dsh.data_axes(mesh) or None
    row = dsh.sanitize(jax.sharding.PartitionSpec(dp, "model"),
                       (b, kvh), mesh)
    batch_ax, kv_ax = row[0], row[1]
    core = functools.partial(_masked_dense_decode_core, head_dim=head_dim,
                             window=window)
    if batch_ax is None and kv_ax is None:
        return core(qq, k, v, positions, count)
    P = jax.sharding.PartitionSpec
    head4 = P(batch_ax, kv_ax, None, None)
    return jax.shard_map(
        core, mesh=mesh,
        in_specs=(head4, head4, head4, P(batch_ax, None), P(batch_ax)),
        out_specs=(head4, head4),
        check_vma=False,
    )(qq, k, v, positions, count)


# ---------------------------------------------------------------------------
# Mesh-native Pallas kernels: shard_map-wrapped block-sparse prefill and
# decode. A raw ``pl.pallas_call`` is opaque to the SPMD partitioner — a
# sharded operand would silently all-gather at the kernel boundary — so
# the kernel wrappers run *inside* shard_map on shard-local shapes: lanes
# (batch) partition over the data axes and KV heads over ``model`` (the
# query groups and the whole dim-blocks of the dim-major K̂ layout ride
# with their KV head, so every model shard streams whole dim-blocks). The
# magnitude top-k block-index tables are computed per shard, mirroring
# ``_shard_mapped_decode_core``: no collectives inside the mapped region.
# An axis whose extent doesn't divide its dimension sanitizes to
# replicated (B=1 admission prefills, MQA's single KV head); batches that
# would leave the cache slot-sharded keep the jnp reference path — see
# ``distributed.sharding.kernel_shardable``.
# ---------------------------------------------------------------------------


def _kernel_row_axes(mesh, batch: int, kv_heads: int):
    """(batch_axis, kv_axis) for the kernel shard_map: lanes over the data
    axes, KV heads over ``model``; an axis whose mesh extent doesn't divide
    its dimension sanitizes to None (replicated)."""
    from repro.distributed import sharding as dsh

    dp = dsh.data_axes(mesh) or None
    row = dsh.sanitize(jax.sharding.PartitionSpec(dp, "model"),
                       (batch, kv_heads), mesh)
    return row[0], row[1]


def shard_mapped_prefill_kernel(mesh, backend, qq, kk, v, *, cfg, aqua,
                                positions, lengths, causal):
    """Run a Pallas prefill backend under shard_map on ``mesh``.

    qq (B, S, KV, G, Dk) / kk (B, S, KV, Dk) / v (B, S, KV, Dv) in model
    layout; ``positions`` must be 1-D (2-D tables route to the dense
    reference before dispatch). Returns (out (B, S, KV, G, Dv), None) —
    kernel backends produce no dense weights."""
    b, s, kvh = qq.shape[0], qq.shape[1], qq.shape[2]
    batch_ax, kv_ax = _kernel_row_axes(mesh, b, kvh)
    if lengths is None:
        # materialize full lengths so the shard_map signature is static
        lengths = jnp.full((b,), s, jnp.int32)

    def core(qs, ks, vs, pos, ls):
        out, _ = backend.prefill(qs, ks, vs, cfg=cfg, aqua=aqua,
                                 positions=pos, lengths=ls, causal=causal)
        return out

    # Even fully-replicated rows (B=1 MQA) stay inside shard_map: a raw
    # pallas_call in the jitted step would face the SPMD partitioner —
    # the exact hazard this wrapper exists to remove.
    P = jax.sharding.PartitionSpec
    out = jax.shard_map(
        core, mesh=mesh,
        in_specs=(P(batch_ax, None, kv_ax, None, None),
                  P(batch_ax, None, kv_ax, None),
                  P(batch_ax, None, kv_ax, None),
                  P(None), P(batch_ax)),
        out_specs=P(batch_ax, None, kv_ax, None, None),
        check_vma=False,
    )(qq, kk, v, positions, lengths)
    return out, None


def shard_mapped_decode_kernel(mesh, backend, q, cache, *, cfg, aqua):
    """Decode twin of :func:`shard_mapped_prefill_kernel`: the block-sparse
    decode kernel on shard-local cache leaves. q (B, KV, G, Dk); the slot
    axis stays whole per shard (the kernel streams full dim-major sequence
    stripes), so per-shard ``NB_sel``/``NB_total`` accounting equals the
    global one. Returns (B, KV, G, Dv)."""
    b, kvh = q.shape[0], q.shape[1]
    batch_ax, kv_ax = _kernel_row_axes(mesh, b, kvh)

    def core(qs, ks, vs, pos, cnt, acc):
        local = kv.AttnCache(k=ks, v=vs, positions=pos, count=cnt,
                             acc_score=acc)
        return backend.decode(qs, local, cfg=cfg, aqua=aqua)

    P = jax.sharding.PartitionSpec
    head4 = P(batch_ax, kv_ax, None, None)
    return jax.shard_map(
        core, mesh=mesh,
        in_specs=(head4, head4, head4, P(batch_ax, None), P(batch_ax),
                  P(batch_ax, kv_ax, None)),
        out_specs=head4,
        check_vma=False,
    )(q, cache.k, cache.v, cache.positions, cache.count, cache.acc_score)


def shard_mapped_paged_decode_kernel(mesh, backend, q, cache, *, cfg, aqua,
                                     part_idx=None):
    """Paged twin of :func:`shard_mapped_decode_kernel`: the block-sparse
    paged decode kernel on shard-local pool + page-table leaves.

    The partitioning follows :func:`distributed.sharding.decode_state_pspec`'s
    paged branch exactly: the page *pool* (k/v/pos/acc) replicates over the
    data axes — pages are lane-global, any lane may map any physical page,
    so table entries are pool-global ids valid unchanged on every data
    shard — while its KV-head axis shards over ``model`` (whole pages ride
    with their head). The page-*table* rows partition with their lanes
    over the data axes, so each data shard's kernel invocation
    scalar-prefetches only its own lane group's page lists and copies
    those pages from its full (KV-sharded) pool slice — zero collectives
    inside the mapped region, exactly like the contiguous kernel threads
    its dim-block indices. q (B, KV, G, Dk);
    returns (B, KV, G, Dv).

    ``part_idx`` (B, KP): hierarchical stage-1 participating-page table.
    It MUST be computed *outside* this wrapper (``core.selection`` on the
    global arrays) — the acc_pool is KV-sharded over ``model``, so a
    shard-local page ranking would give each model shard a different
    participating set. The finished table partitions with its lanes over
    the data axes exactly like the page table
    (``distributed.sharding.page_rank_pspec``) and its entries are
    per-lane logical indices, so each shard's kernel invocation
    scalar-prefetches its own lane group's rows unchanged."""
    b, kvh = q.shape[0], q.shape[1]
    batch_ax, kv_ax = _kernel_row_axes(mesh, b, kvh)

    P = jax.sharding.PartitionSpec
    head4 = P(batch_ax, kv_ax, None, None)
    pool4 = P(None, kv_ax, None, None)
    in_specs = [head4, pool4, pool4, P(None, None), P(None, kv_ax, None),
                P(batch_ax, None), P(batch_ax)]
    operands = [q, cache.k_pool, cache.v_pool, cache.pos_pool,
                cache.acc_pool, cache.page_table, cache.count]
    quant = cache.k_scale is not None
    if quant:
        # per-page quant scales partition with their pages' KV heads over
        # `model` (page axis whole, like the pool); one-scale-per-page
        # (SH=1) arrives replicated — the head slice is then a no-op.
        sh = cache.k_scale.shape[1]
        scale_spec = P(None, kv_ax if sh > 1 else None)
        in_specs += [scale_spec, scale_spec]
        operands += [cache.k_scale, cache.v_scale]
    hier = part_idx is not None
    if hier:
        in_specs.append(P(batch_ax, None))
        operands.append(part_idx)

    def core(qs, kp, vp, pp, ap, pt, cnt, *rest):
        rest = list(rest)
        part = rest.pop() if hier else None
        ks, vs = rest if quant else (None, None)
        local = kv.PagedAttnCache(k_pool=kp, v_pool=vp, pos_pool=pp,
                                  acc_pool=ap, page_table=pt, count=cnt,
                                  k_scale=ks, v_scale=vs)
        if part is None:
            return backend.paged_decode(qs, local, cfg=cfg, aqua=aqua)
        return backend.paged_decode(qs, local, cfg=cfg, aqua=aqua,
                                    part_idx=part)

    return jax.shard_map(
        core, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=head4,
        check_vma=False,
    )(*operands)


# ---------------------------------------------------------------------------
# Chunked (flash-style) attention — pure-XLA memory-efficient path used for
# long-sequence prefill; the S×S score matrix never materializes. On real
# TPU this role is played by kernels/flash_attention.py; the jnp version
# keeps the dry-run/compile path portable and GSPMD-shardable.
# ---------------------------------------------------------------------------

CHUNKED_THRESHOLD = 2048  # use chunked path for sequences >= this


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      head_dim: int, causal: bool = True,
                      window: Optional[int] = None, q_blk: int = 512,
                      k_blk: int = 1024,
                      lengths: Optional[jax.Array] = None) -> jax.Array:
    """q: (B, S, KV, G, D'); k: (B, S, KV, D'); v: (B, S, KV, Dv).

    Online-softmax double scan over (q blocks × k blocks). Scale uses the
    FULL head_dim (AQUA approximates full scores). ``lengths`` (B,) masks
    ragged rows per key block. Returns (B, S, KV, G, Dv).
    """
    b, s, kvh, g, d = q.shape
    dv = v.shape[-1]
    q_blk, k_blk = _rtf.attn_blocks(q_blk, k_blk)
    q_blk = min(q_blk, s)
    k_blk = min(k_blk, s)
    s_real = s
    pad = (-s) % math.lcm(q_blk, k_blk)
    if pad:
        # non-divisible S: pad the sequence and mask the tail via the
        # lengths mechanism (covers causal and non-causal alike); padded
        # query rows are sliced off below
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        s += pad
        if lengths is None:
            lengths = jnp.full((b,), s_real, jnp.int32)
    nq, nk = s // q_blk, s // k_blk
    scale = 1.0 / (float(head_dim) ** 0.5)

    qb = q.reshape(b, nq, q_blk, kvh, g, d).transpose(1, 0, 3, 4, 2, 5)
    kb = k.reshape(b, nk, k_blk, kvh, d).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(b, nk, k_blk, kvh, dv).transpose(1, 0, 3, 2, 4)

    # Window-band restriction (§Perf iteration): for sliding-window
    # attention only the k-blocks intersecting the (window+q_blk) band
    # around the diagonal contribute; iterate exactly those (compute and
    # HBM bytes scale with the window, not the context). For full causal
    # attention iterate the causal prefix of k-blocks per q-block.
    band = None
    if causal and window is not None and window < s:
        band = min(nk, (q_blk + window) // k_blk + 2)

    def outer(_, qi_idx):
        qi, iq = qi_idx                     # (B,KV,G,qb,D), scalar

        def step(c, kj, vj, jk, valid):
            m, l, acc = c
            sij = jnp.einsum("bkgqd,bktd->bkgqt", qi.astype(jnp.float32),
                             kj.astype(jnp.float32)) * scale
            qpos = iq * q_blk + jnp.arange(q_blk)[:, None]
            kpos = jk * k_blk + jnp.arange(k_blk)[None, :]
            mask = jnp.ones((q_blk, k_blk), bool)
            if causal:
                mask &= qpos >= kpos
            if window is not None:
                mask &= kpos > qpos - window
            mask &= valid
            mask = mask[None]                        # (1, q_blk, k_blk)
            if lengths is not None:
                mask = mask & (kpos[None] < lengths[:, None, None])
            sij = jnp.where(mask[:, None, None], sij, NEG_INF)
            m_new = jnp.maximum(m, sij.max(-1))
            p = jnp.exp(sij - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqt,bktd->bkgqd", p, vj.astype(jnp.float32))
            return (m_new, l, acc)

        init = (jnp.full((b, kvh, g, q_blk), NEG_INF, jnp.float32),
                jnp.zeros((b, kvh, g, q_blk), jnp.float32),
                jnp.zeros((b, kvh, g, q_blk, dv), jnp.float32))

        if band is not None:
            last = ((iq + 1) * q_blk - 1) // k_blk  # last needed k-block

            def inner_band(c, j):
                raw = last - (band - 1) + j         # may be < 0 early on
                idx = jnp.clip(raw, 0, nk - 1)
                kj = jax.lax.dynamic_index_in_dim(kb, idx, 0, False)
                vj = jax.lax.dynamic_index_in_dim(vb, idx, 0, False)
                return step(c, kj, vj, idx, raw >= 0), None
            (m, l, acc), _ = _scan(inner_band, init,
                                   jnp.arange(band))
        else:
            def inner(c, kj_idx):
                kj, vj, jk = kj_idx
                return step(c, kj, vj, jk, True), None
            (m, l, acc), _ = _scan(
                inner, init, (kb, vb, jnp.arange(nk)))
        return None, acc / jnp.maximum(l, 1e-30)[..., None]

    _, ob = _scan(outer, None, (qb, jnp.arange(nq)))
    # (nq, B, KV, G, q_blk, Dv) -> (B, S, KV, G, Dv)
    out = ob.transpose(1, 0, 4, 2, 3, 5).reshape(b, s, kvh, g, dv)
    return out[:, :s_real]


# ---------------------------------------------------------------------------
# Attention backend registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttentionBackend:
    """One registry entry (see the module docstring for the contract).

    ``requires_pallas`` backends fall back to the masked-dense reference
    when Pallas is unavailable; ``aqua_native`` backends additionally need
    calibrated AQUA projections (they consume unmasked q̂/k̂).
    ``paged_decode`` (optional) is the decode entry for the block-paged
    KV pool: same query contract as ``decode`` but over a
    ``kv.PagedAttnCache`` (pool + per-lane page table) instead of the
    contiguous slot cache.
    """

    name: str
    prefill: Callable[..., Tuple[jax.Array, Optional[jax.Array]]]
    decode: Optional[Callable[..., jax.Array]] = None
    paged_decode: Optional[Callable[..., jax.Array]] = None
    requires_pallas: bool = False
    aqua_native: bool = False


_BACKENDS: Dict[str, AttentionBackend] = {}


def register_backend(backend: AttentionBackend) -> AttentionBackend:
    _BACKENDS[backend.name] = backend
    return backend


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


def get_backend(name: str) -> AttentionBackend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise KeyError(f"unknown attention backend {name!r}; "
                       f"available: {available_backends()}") from None


def resolve_backend(name: str = "auto",
                    aqua: Optional[AquaConfig] = None) -> AttentionBackend:
    """Map a config-selected backend name to a runnable backend.

    ``auto`` prefers the Pallas kernels when they would run compiled (on
    TPU, or forced via ``runtime_flags.PALLAS_OVERRIDE``) and the jnp
    references otherwise. Explicitly requested kernel backends run in
    interpret mode off-TPU, but fall back to the masked-dense reference
    when Pallas is unavailable there (on a TPU that raises instead);
    AQUA-native backends fall back to flash / dense when AQUA is disabled
    (no projections to select over).
    """
    aqua_on = aqua is not None and aqua.enabled
    if name in (None, "", "auto"):
        if _rtf.kernels_preferred():
            name = "aqua-block-sparse" if aqua_on else "flash"
        else:
            name = "aqua-masked-dense" if aqua_on else "dense-jnp"
    be = get_backend(name)
    if be.requires_pallas and not _rtf.pallas_available():
        if _rtf.on_tpu():
            # a TPU serving the jnp reference in place of a requested
            # kernel would look like a working kernel path
            raise RuntimeError(
                f"attention backend {be.name!r} needs Pallas, which does "
                "not import on this TPU install")
        be = get_backend("aqua-masked-dense" if aqua_on else "dense-jnp")
    if be.aqua_native and not aqua_on:
        be = get_backend("flash" if _rtf.kernels_preferred() else "dense-jnp")
    return be


def _dense_jnp_prefill(qq, kk, v, *, cfg, aqua, positions, lengths, causal):
    """Materialized-score reference; switches to the chunked online-softmax
    scan for long causal sequences (the S×S matrix never materializes)."""
    s = qq.shape[1]
    if s >= CHUNKED_THRESHOLD and causal and positions.ndim == 1:
        out = chunked_attention(qq, kk, v, head_dim=cfg.head_dim,
                                causal=True, window=cfg.window,
                                lengths=lengths)
        return out, None
    scores = jnp.einsum("bskgd,btkd->bkgst", qq, kk)
    scores = scores.astype(jnp.float32) / jnp.sqrt(float(cfg.head_dim))
    kpos = positions if positions.ndim == 2 else positions[None]
    mask = None
    if causal:
        qpos = kpos
        mask = qpos[:, None, None, :, None] >= kpos[:, None, None, None, :]
        if cfg.window is not None:
            mask &= (kpos[:, None, None, None, :]
                     > qpos[:, None, None, :, None] - cfg.window)
    if lengths is not None:
        lmask = kpos[:, None, None, None, :] < lengths[:, None, None, None,
                                                       None]
        mask = lmask if mask is None else mask & lmask
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", weights.astype(v.dtype), v)
    return out, weights


def _flash_prefill(qq, kk, v, *, cfg, aqua, positions, lengths, causal):
    """Pallas flash kernel on head-major layout. Ragged lengths, 2-D
    positions, non-causal shapes (sequence padding is only safe under a
    causal mask) and AQUA-Memory-sliced heads (the kernel assumes
    dk == dv) are delegated to the dense reference."""
    if (not causal or positions.ndim == 2 or lengths is not None
            or qq.shape[-1] != v.shape[-1]):
        return _dense_jnp_prefill(qq, kk, v, cfg=cfg, aqua=aqua,
                                  positions=positions, lengths=lengths,
                                  causal=causal)
    from repro.kernels import ops as kops
    b, s, kvh, g, d = qq.shape
    dv = v.shape[-1]
    qf = qq.transpose(0, 2, 3, 1, 4).reshape(b, kvh * g, s, d)
    kf = kk.transpose(0, 2, 1, 3)
    vf = v.transpose(0, 2, 1, 3)
    blk = min(128, _ceil_to(s, 8))
    spad = _ceil_to(s, blk)
    if spad != s:
        pad = ((0, 0), (0, 0), (0, spad - s), (0, 0))
        qf, kf, vf = jnp.pad(qf, pad), jnp.pad(kf, pad), jnp.pad(vf, pad)
    of = kops.flash_attention(qf, kf, vf, causal=True, window=cfg.window,
                              q_blk=blk, k_blk=blk)[:, :, :s]
    out = of.reshape(b, kvh, g, s, dv).transpose(0, 3, 1, 2, 4)
    return out, None


def _aqua_block_sparse_prefill(qh, kh, v, *, cfg, aqua, positions, lengths,
                               causal):
    """AQUA block-sparse chunked-prefill kernel: per-chunk dim-block
    selection over unmasked q̂, dim-major K̂ streaming (kernels/aqua_prefill).
    Scores are scaled by the FULL head_dim — the paper approximates full
    scores even when k̂ is statically sliced."""
    from repro.kernels import ops as kops
    b, s, kvh, g, dk = qh.shape
    dv = v.shape[-1]
    bd = aqua.block_dims
    qf = qh.transpose(0, 2, 3, 1, 4).reshape(b, kvh * g, s, dk)
    kf = kh.transpose(0, 2, 1, 3)
    vf = v.transpose(0, 2, 1, 3)
    of = kops.aqua_prefill(qf, kf, vf, lengths, k_ratio=aqua.k_ratio,
                           block_dims=bd, q_blk=aqua.prefill_q_blk,
                           k_blk=aqua.prefill_k_blk, causal=causal,
                           window=cfg.window,
                           scale=1.0 / float(cfg.head_dim) ** 0.5)
    out = of.reshape(b, kvh, g, s, dv).transpose(0, 3, 1, 2, 4)
    return out, None


def _aqua_block_sparse_decode(q_hat, cache, *, cfg, aqua):
    """AQUA block-sparse decode kernel over the contiguous slot cache.
    q_hat: (B, KV, G, Dk) projected (unmasked) query. Returns
    (B, KV, G, Dv)."""
    from repro.kernels import ops as kops
    b, kvh, g, dk = q_hat.shape
    bd = aqua.block_dims
    qf = q_hat.reshape(b, kvh * g, dk)
    lengths = jnp.minimum(cache.count, cache.num_slots)
    seq_blk = min(aqua.decode_seq_blk, _ceil_to(cache.num_slots, 8))
    out = kops.aqua_decode(qf, cache.k, cache.v, lengths,
                           k_ratio=aqua.k_ratio, block_dims=bd,
                           seq_blk=seq_blk,
                           scale=1.0 / float(cfg.head_dim) ** 0.5)
    return out.reshape(b, kvh, g, -1)


def _aqua_block_sparse_paged_decode(q_hat, cache: kv.PagedAttnCache, *,
                                    cfg, aqua, part_idx=None):
    """Paged AQUA block-sparse decode: the kernel reads each lane's pool
    pages HBM→VMEM through its scalar-prefetched page table
    (kernels/aqua_decode.aqua_paged_decode_attention); no gathered lane
    view is ever materialized.
    ``part_idx`` (B, KP) is the hierarchical stage-1 participating-page
    table (``core.selection``), or None to walk every page."""
    from repro.kernels import ops as kops
    b, kvh, g, dk = q_hat.shape
    qf = q_hat.reshape(b, kvh * g, dk)
    lengths = jnp.minimum(cache.count, cache.num_slots)
    out = kops.aqua_paged_decode(qf, cache.k_pool, cache.v_pool,
                                 cache.page_table, lengths,
                                 cache.k_scale, cache.v_scale, part_idx,
                                 k_ratio=aqua.k_ratio,
                                 block_dims=aqua.block_dims,
                                 scale=1.0 / float(cfg.head_dim) ** 0.5)
    return out.reshape(b, kvh, g, -1)


register_backend(AttentionBackend("dense-jnp", _dense_jnp_prefill))
register_backend(AttentionBackend("flash", _flash_prefill,
                                  requires_pallas=True))
register_backend(AttentionBackend("aqua-masked-dense", _dense_jnp_prefill))
register_backend(AttentionBackend("aqua-block-sparse",
                                  _aqua_block_sparse_prefill,
                                  decode=_aqua_block_sparse_decode,
                                  paged_decode=_aqua_block_sparse_paged_decode,
                                  requires_pallas=True, aqua_native=True))


# ---------------------------------------------------------------------------
# Prefill attention (full sequence)
# ---------------------------------------------------------------------------


def prefill_attention(params: dict, x: jax.Array, cfg: AttentionConfig,
                      aqua: Optional[AquaConfig] = None,
                      proj: Optional[jax.Array] = None,
                      positions: Optional[jax.Array] = None,
                      kv_x: Optional[jax.Array] = None,
                      return_aux: bool = False,
                      lengths: Optional[jax.Array] = None):
    """Sequence attention, dispatched through the backend registry
    (``cfg.backend``). ``kv_x`` enables cross-attention (keys/values from
    the encoder); in that mode AQUA and causal masking are bypassed unless
    configured otherwise. ``lengths`` (B,) masks ragged rows: keys at or
    beyond a row's length are never attended.

    Returns out (B, S, d_model) [, aux dict with q/k activations & weights].
    """
    b, s, _ = x.shape
    if kv_x is not None and lengths is not None:
        raise ValueError(
            "`lengths` masks self-attention keys; ragged cross-attention "
            "would need encoder-side lengths (unsupported)")
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)
    src = x if kv_x is None else kv_x

    q = jnp.einsum("bsm,mkgd->bskgd", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsm,mkd->bskd", src, params["wk"].astype(src.dtype))
    v = jnp.einsum("bsm,mkd->bskd", src, params["wv"].astype(src.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if cfg.use_rope and kv_x is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    aqua_on = aqua is not None and aqua.enabled
    qh, kh = _aqua_project(q, k, aqua, proj, cfg.head_dim)

    causal = cfg.causal and kv_x is None
    backend = resolve_backend(cfg.backend, aqua=aqua)
    if kv_x is not None or positions.ndim == 2:
        # cross-attention / per-row position tables: reference path only
        backend = get_backend("dense-jnp")
    if backend.name == "aqua-block-sparse":
        # The kernel needs dim-*block* selection; block_dims=1 is the
        # paper's per-dim semantics — never silently coarsen it (numerics
        # must not depend on which backend a platform resolved to). The
        # masked-q identity is exact over masked inputs, so on TPU the
        # flash kernel serves per-dim selection at identical numerics
        # without materializing S×S scores; jnp reference elsewhere.
        if (not aqua_on or aqua.block_dims <= 1
                or kh.shape[-1] % aqua.block_dims != 0):
            backend = get_backend("flash" if _rtf.kernels_preferred()
                                  else "aqua-masked-dense")
    kernel_mesh = None
    if backend.requires_pallas and decode_mesh() is not None:
        # mesh-native serving: run the Pallas kernel under shard_map
        # (lanes × KV heads, per-shard block-index tables); only axis
        # extents that would leave the cache slot-sharded keep the
        # GSPMD-shardable jnp reference path
        from repro.distributed import sharding as dsh
        if dsh.kernel_shardable(decode_mesh(), cfg,
                                aqua if backend.aqua_native else None,
                                batch=b):
            kernel_mesh = decode_mesh()
        else:
            _log_mesh_kernel_fallback(backend.name, "prefill",
                                      REASON_NONDIVISIBLE_MESH)
            backend = get_backend("aqua-masked-dense" if aqua_on
                                  else "dense-jnp")
    if backend.name == "aqua-block-sparse":
        qq, kk = qh, kh          # unmasked: kernel selects dim-blocks
    elif aqua_on:
        # masked-q identity: per-query magnitude mask, materialized only
        # on the reference paths (the kernels select inside the wrapper)
        qq, kk = qh * _aqua_mask(qh, aqua, cfg.head_dim), kh
    else:
        qq, kk = q, k

    if kernel_mesh is not None:
        out, weights = shard_mapped_prefill_kernel(
            kernel_mesh, backend, qq, kk, v, cfg=cfg, aqua=aqua,
            positions=positions, lengths=lengths, causal=causal)
    else:
        out, weights = backend.prefill(qq, kk, v, cfg=cfg, aqua=aqua,
                                       positions=positions, lengths=lengths,
                                       causal=causal)
    out = out.astype(v.dtype)
    out = jnp.einsum("bskgd,kgdm->bsm", out, params["wo"].astype(x.dtype))
    if return_aux:
        aux = {"q": q, "k": k, "weights": weights,
               "q_hat": qh if aqua_on else None,
               "k_hat": kh if aqua_on else None}
        return out, aux
    return out


# ---------------------------------------------------------------------------
# Prefill -> cache handoff
# ---------------------------------------------------------------------------


def build_cache_from_prefill(params: dict, x: jax.Array, cfg: AttentionConfig,
                             aqua: Optional[AquaConfig],
                             proj: Optional[jax.Array],
                             max_seq: int,
                             lengths: Optional[jax.Array] = None
                             ) -> kv.AttnCache:
    """Construct the decode cache after a prefill pass (serving engine).

    ``lengths`` (B,) marks ragged rows: their ``count`` starts at the valid
    prefix length, so decode masks the padding keys and the next token
    lands at the right position/slot. Only the contiguous full-cache
    policy places ragged rows coherently — window rings and H2O eviction
    place slots assuming a rectangular batch, so combining them with
    ``lengths`` raises rather than silently corrupting generations.
    """
    b, s, _ = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)
    q, k, v = qkv(params, x, cfg, positions)
    head_dim = cfg.head_dim
    if aqua is not None and aqua.enabled:
        k = project_k(k, proj)[..., :aqua.kept_dims(head_dim)]
    dk, dv = k.shape[-1], v.shape[-1]

    h2o_budget = None
    if aqua is not None and aqua.h2o_ratio < 1.0:
        h2o_budget = max(8, int(aqua.h2o_ratio * max_seq))
    if lengths is not None and (cfg.window is not None
                                or h2o_budget is not None):
        raise ValueError(
            "ragged `lengths` require the contiguous full-cache policy; "
            "sliding-window and H2O caches place slots assuming a "
            "rectangular batch — prefill unpadded rows separately or drop "
            "`lengths`")
    count = jnp.full((b,), s, jnp.int32) if lengths is None else lengths
    slots = kv.cache_slots(max_seq, cfg.window, h2o_budget)
    cache = kv.init_attn_cache(b, cfg.num_kv_heads, slots, dk, dv, k.dtype)

    if h2o_budget is not None and s > slots:
        # H2O prefill: accumulated (approximate, if AQUA) attention mass.
        # NB: k above is already projected + sliced when AQUA is on, so we
        # only transform the query side here.
        qq = q
        if aqua.enabled and proj is not None:
            qq = project_q(q, proj)[..., :aqua.kept_dims(head_dim)]
            m = aqua_lib.magnitude_mask(qq, aqua.topk_dims(head_dim),
                                        block_dims=aqua.block_dims)
            qq = qq * m
        sc = jnp.einsum("bskgd,btkd->bkgst", qq, k)
        sc = sc.astype(jnp.float32) / jnp.sqrt(float(head_dim))
        causal = positions[:, None] >= positions[None, :]
        if cfg.window is not None:
            # combined H2O+window: out-of-window keys never receive mass,
            # so the heavy-hitter statistic only ranks in-window tokens
            causal &= positions[None, :] > positions[:, None] - cfg.window
        sc = jnp.where(causal[None, None, None], sc, NEG_INF)
        w = jax.nn.softmax(sc, axis=-1)
        acc = w.sum(axis=(2, 3))  # (B, KV, S) summed over groups & queries
        recent = max(1, int(aqua.h2o_recent_frac * slots))
        keep_hh = slots - recent
        score_tok = acc.sum(axis=1)  # (B, S)
        # protect the recent window from scored selection
        score_tok = score_tok.at[:, s - recent:].set(-jnp.inf)
        _, hh_idx = jax.lax.top_k(score_tok, keep_hh)
        recent_idx = jnp.broadcast_to(jnp.arange(s - recent, s), (b, recent))
        sel = jnp.concatenate([jnp.sort(hh_idx, axis=-1), recent_idx], axis=-1)
        # gather selected tokens: (S, KV, D)[sel] -> (slots, KV, D) -> (KV, slots, D)
        take = jax.vmap(lambda a, i: a[i].transpose(1, 0, 2), in_axes=(0, 0))
        cache = kv.AttnCache(
            k=take(k, sel), v=take(v, sel),
            positions=jnp.take_along_axis(
                jnp.broadcast_to(positions, (b, s)), sel, axis=-1),
            count=jnp.full((b,), s, jnp.int32),
            acc_score=jnp.take_along_axis(acc, sel[:, None, :], axis=-1),
        )
        return cache

    # full / window caches: last `slots` tokens, ring-consistent placement.
    # Those slots form one contiguous run from slot 0 (rotated when a ring
    # has wrapped), so the cache is built by rolling and padding — no
    # scatter, which the TPU compiler fails on once fused with the K/V
    # projections.
    start = max(0, s - slots)
    shift = start % slots if cfg.window is not None else 0

    def place(a, axis, fill=0):            # a holds s - start tokens
        a = jnp.roll(a, shift, axis=axis) if shift else a
        pad = [(0, 0)] * a.ndim
        pad[axis] = (0, slots - (s - start))
        return jnp.pad(a, pad, constant_values=fill)

    return kv.AttnCache(
        k=place(k[:, start:].transpose(0, 2, 1, 3), 2),
        v=place(v[:, start:].transpose(0, 2, 1, 3), 2),
        positions=jnp.broadcast_to(place(positions[start:], 0, fill=-1),
                                   (b, slots)),
        count=count,
        acc_score=cache.acc_score,
    )


# ---------------------------------------------------------------------------
# Prefix-shared tail prefill (paged serving)
# ---------------------------------------------------------------------------


def prefixed_tail_attention(params: dict, x: jax.Array, cfg: AttentionConfig,
                            aqua: Optional[AquaConfig],
                            proj: Optional[jax.Array], *,
                            prefix_k: jax.Array, prefix_v: jax.Array,
                            prefix_positions: jax.Array,
                            prefix_len: jax.Array, positions: jax.Array,
                            lengths: Optional[jax.Array] = None,
                            select_q_blk: Optional[int] = None
                            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Causal attention of a prompt *tail* against a read-only cache
    prefix plus itself — the zero-recompute admission path for
    prefix-shared paged serving, and the per-chunk step of chunked
    prefill.

    x: (1, T, d_model) tail activations; ``prefix_k`` (1, KV, S, Dk') /
    ``prefix_v`` (1, KV, S, Dv) are the lane's gathered cache view (keys
    already projected + sliced when AQUA is on); ``prefix_positions``
    (1, S) with -1 empties; prefix keys are valid where their position is
    in ``[0, prefix_len)``. ``positions`` (1, T) absolute tail positions
    (``prefix_len + arange``); ``lengths`` (1,) masks ragged tail padding.

    Runs the masked-dense reference path (admission-time work, exactly
    like B=1 graft prefills under a mesh). ``select_q_blk`` (static)
    switches the AQUA selection from per-query to per-tile aggregation
    (:func:`_chunk_tile_mask`) — the chunked-prefill engine passes the
    kernel's ``prefill_q_blk`` there so chunks of a fresh prompt select
    exactly the dim-blocks the monolithic kernel admission would.
    Returns
    (out (1, T, d_model), k_cache (1, T, KV, Dk'), v (1, T, KV, Dv)) with
    ``k_cache`` in the cache's stored form (projected/sliced under AQUA).
    """
    q, k, v = qkv(params, x, cfg, positions)
    aqua_on = aqua is not None and aqua.enabled
    qh, kh = _aqua_project(q, k, aqua, proj, cfg.head_dim)
    if aqua_on:
        if select_q_blk is not None:
            qq = qh * _chunk_tile_mask(qh, aqua, select_q_blk, lengths)
        else:
            qq = qh * _aqua_mask(qh, aqua, cfg.head_dim)
        kk = kh
    else:
        qq, kk = q, k

    scale = 1.0 / jnp.sqrt(float(cfg.head_dim))
    qpos = positions                                     # (1, T)
    ppos = prefix_positions                              # (1, S)
    sp = jnp.einsum("bskgd,bktd->bkgst", qq, prefix_k.astype(qq.dtype))
    sp = sp.astype(jnp.float32) * scale
    mp = ((ppos >= 0) & (ppos < prefix_len))[:, None, None, None, :]
    if cfg.window is not None:
        mp = mp & (ppos[:, None, None, None, :]
                   > qpos[:, None, None, :, None] - cfg.window)
    st = jnp.einsum("bskgd,btkd->bkgst", qq, kk)
    st = st.astype(jnp.float32) * scale
    mt = qpos[:, None, None, :, None] >= qpos[:, None, None, None, :]
    if cfg.window is not None:
        mt &= qpos[:, None, None, None, :] > \
            qpos[:, None, None, :, None] - cfg.window
    if lengths is not None:
        t = q.shape[1]
        mt &= (jnp.arange(t)[None, :] < lengths[:, None]
               )[:, None, None, None, :]
    scores = jnp.concatenate([jnp.where(mp, sp, NEG_INF),
                              jnp.where(mt, st, NEG_INF)], axis=-1)
    weights = jax.nn.softmax(scores, axis=-1)
    vals = jnp.concatenate([prefix_v.astype(v.dtype),
                            v.transpose(0, 2, 1, 3)], axis=2)
    out = jnp.einsum("bkgst,bktd->bskgd", weights.astype(v.dtype), vals)
    out = jnp.einsum("bskgd,kgdm->bsm", out.astype(v.dtype),
                     params["wo"].astype(x.dtype))
    return out, kk, v


# ---------------------------------------------------------------------------
# Decode attention (single step, slot cache)
# ---------------------------------------------------------------------------


def decode_attention(params: dict, x_t: jax.Array, cache: kv.AttnCache,
                     cfg: AttentionConfig, aqua: Optional[AquaConfig] = None,
                     proj: Optional[jax.Array] = None,
                     cross: Optional[Tuple[jax.Array, jax.Array]] = None,
                     write_mask: Optional[jax.Array] = None,
                     ) -> Tuple[jax.Array, kv.AttnCache]:
    """One decode step. x_t: (B, d_model). Returns (out (B, d_model), cache).

    ``cross`` = (k_enc, v_enc) each (B, S_enc, KV, D) for cross-attention
    layers (whisper decoder); those bypass the cache entirely.

    ``write_mask`` (B,) bool freezes masked-off rows' cache (no K/V write,
    no count advance, no H2O accumulation) — the continuous-batching
    engine's inactive lanes still flow through the batched step at static
    shape but their state stays bit-identical.
    """
    b = x_t.shape[0]
    if cross is not None:
        k_enc, v_enc = cross
        q = jnp.einsum("bm,mkgd->bkgd", x_t, params["wq"].astype(x_t.dtype))
        if cfg.qkv_bias:
            q = q + params["bq"].astype(x_t.dtype)
        if cfg.qk_norm:
            q = rms_norm(q, params["q_norm"])
        sc = jnp.einsum("bkgd,bskd->bkgs", q, k_enc).astype(jnp.float32)
        w = jax.nn.softmax(sc / jnp.sqrt(float(cfg.head_dim)), axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", w.astype(v_enc.dtype), v_enc)
        out = jnp.einsum("bkgd,kgdm->bm", out, params["wo"].astype(x_t.dtype))
        return out, cache

    pos = cache.count  # (B,) position of the incoming token
    q, k, v = qkv(params, x_t[:, None, :], cfg, pos[:, None])
    q, k_t, v_t = q[:, 0], k[:, 0], v[:, 0]  # (B,KV,G,D), (B,KV,D)

    head_dim = cfg.head_dim
    aqua_on = aqua is not None and aqua.enabled
    if aqua_on:
        with jax.named_scope("aqua.project"):
            qh = jnp.einsum("bkgd,kde->bkge", q, proj.astype(q.dtype))
            kh = jnp.einsum("bkd,kde->bke", k_t, proj.astype(k_t.dtype))
        kept = aqua.kept_dims(head_dim)
        q, k_t = qh[..., :kept], kh[..., :kept]

    h2o = aqua is not None and aqua.enabled and aqua.h2o_ratio < 1.0
    recent_len = 0
    if h2o:
        recent_len = max(1, int(aqua.h2o_recent_frac * cache.num_slots))
    if isinstance(cache, kv.PagedAttnCache):
        slot, evict = kv.paged_select_slot(cache, window=cfg.window, h2o=h2o,
                                           recent_len=recent_len)
        cache = kv.paged_insert(cache, slot, k_t, v_t,
                                write_mask=write_mask, evict_page=evict)
        return _paged_decode_product(params, x_t, q, cache, cfg, aqua,
                                     h2o=h2o, write_mask=write_mask)
    slot = kv.select_slot(cache, window=cfg.window, h2o=h2o,
                          recent_len=recent_len)
    cache = kv.insert(cache, slot, k_t, v_t, write_mask=write_mask)

    # Registry dispatch: the block-sparse decode kernel serves the
    # contiguous full-cache policy (no ring buffer, no eviction — those
    # need the masked-dense path's per-slot position masking / weights).
    # Under a serving mesh the kernel runs shard_mapped (lanes over the
    # data axes, KV heads over `model`, per-shard block-index tables);
    # only non-divisible axis extents keep the shard_map/jnp reference.
    backend = resolve_backend(cfg.backend, aqua=aqua)
    kernel_ok = (backend.decode is not None and aqua_on and not h2o
                 and cfg.window is None and aqua.block_dims > 1
                 and q.shape[-1] % aqua.block_dims == 0)
    kernel_mesh = None
    if kernel_ok and decode_mesh() is not None:
        from repro.distributed import sharding as dsh
        if dsh.kernel_shardable(decode_mesh(), cfg, aqua, batch=b):
            kernel_mesh = decode_mesh()
        else:
            _log_mesh_kernel_fallback(backend.name, "decode",
                                      REASON_NONDIVISIBLE_MESH)
            kernel_ok = False
    if kernel_ok:
        if kernel_mesh is not None:
            out = shard_mapped_decode_kernel(kernel_mesh, backend, q, cache,
                                             cfg=cfg, aqua=aqua)
        else:
            out = backend.decode(q, cache, cfg=cfg, aqua=aqua)
        out = jnp.einsum("bkgd,kgdm->bm", out, params["wo"].astype(x_t.dtype))
        return out, cache

    # masked-dense reference: materialize the per-query magnitude mask;
    # shard_map-wrapped (lanes × KV heads) when a serving mesh is installed
    qq = q * _aqua_mask(q, aqua, head_dim) if aqua_on else q
    mesh = decode_mesh()
    if mesh is not None:
        out, weights = _shard_mapped_decode_core(
            mesh, qq, cache.k, cache.v, cache.positions, cache.count,
            head_dim=head_dim, window=cfg.window)
    else:
        out, weights = _masked_dense_decode_core(
            qq, cache.k, cache.v, cache.positions, cache.count,
            head_dim=head_dim, window=cfg.window)
    if h2o:
        cache = kv.accumulate_h2o(cache, weights, write_mask=write_mask)
    out = jnp.einsum("bkgd,kgdm->bm", out, params["wo"].astype(x_t.dtype))
    return out, cache


def _paged_decode_product(params, x_t: jax.Array, q: jax.Array,
                          cache: kv.PagedAttnCache, cfg: AttentionConfig,
                          aqua: Optional[AquaConfig], *, h2o: bool,
                          write_mask: Optional[jax.Array]
                          ) -> Tuple[jax.Array, kv.PagedAttnCache]:
    """Read side of paged decode attention (the insert already ran).

    ``q`` is the projected (unmasked) query when AQUA is on. Dispatch
    mirrors the contiguous path exactly: the block-sparse Pallas kernel
    serves the full-cache policy (page table scalar-prefetched), running
    shard_mapped under a serving mesh (lane-partitioned page tables over
    the data axes, the lane-global pool KV-sharded over ``model``; see
    :func:`shard_mapped_paged_decode_kernel`) whenever
    ``distributed.sharding.kernel_shardable`` admits the geometry.
    Everything else — window rings, page-granular H2O, non-divisible
    extents, pages that don't tile the kernel's sequence blocks — runs
    the masked-dense reference on the gathered lane view, which is
    slot-for-slot identical to the contiguous cache layout.

    Hierarchical token sparsity (``use_token_sparsity`` installed by the
    engine) resolves the step's stage-1 participating-page table here,
    *before* any shard_map — the acc_pool is KV-sharded over ``model``
    under a mesh, so ranking must see the global pool (see
    :func:`shard_mapped_paged_decode_kernel`). The kernel path streams
    only participating pages; the reference path masks the same slots
    (positions < 0 are invalid in ``kv.valid_mask_from``), so both paths
    attend exactly the plan's token set.
    """
    aqua_on = aqua is not None and aqua.enabled
    head_dim = cfg.head_dim
    b = q.shape[0]
    backend = resolve_backend(cfg.backend, aqua=aqua)
    # stage-1 page participation: engages only where DispatchPlan's
    # token-sparsity predicate says so (no window, no H2O eviction —
    # REASON_TOKEN_*); a full keep (kept >= pages_per_lane) is a no-op.
    tok = token_sparsity()
    part_idx = None
    if (tok is not None and not h2o and cfg.window is None
            and tok[0] < cache.pages_per_lane):
        from repro.core import selection
        part_idx = selection.participating_pages(
            cache.acc_pool, cache.page_table, cache.count,
            page_size=cache.page_size, kept_pages=tok[0],
            pin_recent_pages=tok[1])
    kernel_ok = (backend.paged_decode is not None and aqua_on and not h2o
                 and cfg.window is None and aqua.block_dims > 1
                 and q.shape[-1] % aqua.block_dims == 0)
    if kernel_ok and cache.k_hot is not None:
        # mixed-precision hot residents only exist in the reference
        # path's dequantized lane view — the kernel reads raw int8 pages
        if decode_mesh() is not None:
            _log_mesh_kernel_fallback(backend.name, "decode",
                                      REASON_QUANT_RESIDENCY)
        kernel_ok = False
    kernel_mesh = None
    if kernel_ok and decode_mesh() is not None:
        from repro.distributed import sharding as dsh
        if dsh.kernel_shardable(decode_mesh(), cfg, aqua, batch=b,
                                page_size=cache.page_size):
            kernel_mesh = decode_mesh()
        else:
            reason = (REASON_PAGE_GEOMETRY
                      if cache.page_size % dsh.KERNEL_PAGE_MULTIPLE != 0
                      else REASON_NONDIVISIBLE_MESH)
            _log_mesh_kernel_fallback(backend.name, "decode", reason)
            kernel_ok = False
    if kernel_ok and cache.page_size % 8 != 0:
        # single-device: quietly keep the reference (same page-geometry
        # constraint kernel_shardable applies on the mesh path)
        kernel_ok = False
    if kernel_ok:
        if kernel_mesh is not None:
            out = shard_mapped_paged_decode_kernel(kernel_mesh, backend, q,
                                                   cache, cfg=cfg, aqua=aqua,
                                                   part_idx=part_idx)
        elif part_idx is not None:
            out = backend.paged_decode(q, cache, cfg=cfg, aqua=aqua,
                                       part_idx=part_idx)
        else:
            out = backend.paged_decode(q, cache, cfg=cfg, aqua=aqua)
        out = jnp.einsum("bkgd,kgdm->bm", out, params["wo"].astype(x_t.dtype))
        return out, cache

    qq = q * _aqua_mask(q, aqua, head_dim) if aqua_on else q
    view = kv.paged_lane_view(cache)
    positions = view.positions
    if part_idx is not None:
        # reference twin of the kernel's participation: non-participating
        # slots' positions drop to -1, which valid_mask_from masks off —
        # the reference attends exactly the kernel path's token set.
        from repro.core import selection
        slot_ok = selection.participation_slot_mask(
            part_idx, page_size=cache.page_size, num_slots=cache.num_slots)
        positions = jnp.where(slot_ok, positions, -1)
    mesh = decode_mesh()
    if mesh is not None:
        out, weights = _shard_mapped_decode_core(
            mesh, qq, view.k, view.v, positions, view.count,
            head_dim=head_dim, window=cfg.window)
    else:
        out, weights = _masked_dense_decode_core(
            qq, view.k, view.v, positions, view.count,
            head_dim=head_dim, window=cfg.window)
    if h2o:
        cache = kv.paged_accumulate_h2o(cache, weights,
                                        write_mask=write_mask)
    out = jnp.einsum("bkgd,kgdm->bm", out, params["wo"].astype(x_t.dtype))
    return out, cache
