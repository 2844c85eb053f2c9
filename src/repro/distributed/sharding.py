"""Sharding rules: parameter/optimizer/cache PartitionSpecs for the
production meshes.

Design (DESIGN.md §3):
  * ``pod`` × ``data`` is the pure data-parallel domain (batch axis).
  * ``model`` carries tensor parallelism (attention KV-heads / query
    groups, FFN hidden, vocab) and expert parallelism (MoE expert axis).
  * ``long_500k`` (batch=1) shards the decode cache's *sequence* axis over
    ``data`` (context parallelism); GSPMD inserts the flash-decode-style
    combine collectives.

Rules are name+shape based and **divisibility-sanitized**: a candidate
axis that doesn't divide the dimension falls back to the next candidate
(e.g. qwen2-moe's 60 experts can't split 16 ways -> expert-ff TP instead;
MQA's single KV head -> shard query groups / head_dim instead; batch=1
-> replicate batch). This makes every (arch × shape × mesh) cell feasible
without per-arch hand-tuning.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(mesh.shape[a] for a in axes)


def sanitize(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop spec entries whose mesh-axis size doesn't divide the dim, or
    that name an axis the mesh doesn't carry (data-only serving meshes
    have no ``model`` axis)."""
    out = []
    for i in range(len(shape)):
        s = spec[i] if i < len(spec) else None
        if s is not None:
            axes = (s,) if isinstance(s, str) else tuple(s)
            if any(a not in mesh.shape for a in axes):
                s = None
            elif shape[i] % _axis_size(mesh, s) != 0:
                s = None
        out.append(s)
    return P(*out)


def _spec_at(ndim: int, dim_from_end: int, axes) -> P:
    lst = [None] * ndim
    if 0 <= ndim + dim_from_end < ndim:
        lst[ndim + dim_from_end] = axes
    return P(*lst)


def _first_feasible(cands: Sequence[P], shape, mesh: Mesh) -> P:
    for c in cands:
        if len(shape) < len(c):
            continue
        if sanitize(c, shape, mesh) == P(*c, *([None] * (len(shape) - len(c)))):
            return sanitize(c, shape, mesh)
    return P(*([None] * len(shape)))


def path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

_REPLICATED_NAMES = {"ln", "ln1", "ln2", "ln_x", "ln_f", "enc_ln", "q_norm",
                     "k_norm", "out_norm", "lam", "dt_bias", "b"}


def param_pspec(path, shape, mesh: Mesh, model_axis: str = "model") -> P:
    name = path_str(path).split("/")[-1]
    nd = len(shape)
    m = model_axis
    if name in _REPLICATED_NAMES or nd == 0:
        return P(*([None] * nd))
    cands = {
        "wq": [_spec_at(nd, -3, m), _spec_at(nd, -2, m)],
        "wk": [_spec_at(nd, -2, m), _spec_at(nd, -1, m)],
        "wv": [_spec_at(nd, -2, m), _spec_at(nd, -1, m)],
        "wo": [_spec_at(nd, -4, m), _spec_at(nd, -3, m)],
        "bq": [_spec_at(nd, -3, m), _spec_at(nd, -2, m)],
        "bk": [_spec_at(nd, -2, m)],
        "bv": [_spec_at(nd, -2, m)],
        "w2": [_spec_at(nd, -2, m)],
        "router": [_spec_at(nd, -1, m)],
        "table": [_spec_at(nd, -2, m), _spec_at(nd, -1, m)],
        "pos": [_spec_at(nd, -1, m)],
        "wout": [_spec_at(nd, -2, m)],
        "out_proj": [_spec_at(nd, -2, m)],
        "a_log": [_spec_at(nd, -1, m)],
        "d_skip": [_spec_at(nd, -1, m)],
    }.get(name)
    if cands is None:
        if name in ("w1", "w3"):
            if nd >= 4:  # MoE experts (L, E, dm, f): EP first, then ff-TP
                cands = [_spec_at(nd, -3, m), _spec_at(nd, -1, m)]
            else:
                cands = [_spec_at(nd, -1, m)]
        else:
            # generic projections (in_proj, wx, wgate, wr, wi, conv_w,
            # conv_b, shared_gate, patch w, ...): shard the output dim.
            cands = [_spec_at(nd, -1, m)]
    return _first_feasible(cands, shape, mesh)


def zero1_pspec(path, shape, mesh: Mesh, model_axis: str = "model") -> P:
    """ZeRO-1: optimizer-state sharding. Start from the parameter's TP spec
    and additionally shard the largest still-replicated dim over the data
    axes — Adam moments drop from params-bytes to params-bytes/(data·model)
    per device. The update runs on shards; GSPMD all-gathers the new params
    (same volume as the gradient reduce-scatter it replaces)."""
    base = param_pspec(path, shape, mesh, model_axis)
    dp = data_axes(mesh)
    if not dp:
        return base
    dims = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in dims:
        if base[i] is None and shape[i] % _axis_size(mesh, dp) == 0:
            lst = list(base) + [None] * (len(shape) - len(base))
            lst[i] = dp
            return P(*lst)
    return base


def make_param_shardings(params, mesh: Mesh, model_axis: str = "model"):
    def one(path, leaf):
        return NamedSharding(mesh, param_pspec(path, leaf.shape, mesh,
                                               model_axis))
    return jax.tree_util.tree_map_with_path(one, params)


# ---------------------------------------------------------------------------
# activation / batch / decode-state rules
# ---------------------------------------------------------------------------


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_pspec(mesh: Mesh, shape, extra_dims: int = 1) -> P:
    """(B, ...) activations: shard batch over pod×data if divisible."""
    dp = data_axes(mesh)
    spec = P(dp, *([None] * (len(shape) - 1)))
    s = sanitize(spec, shape, mesh)
    if s[0] is None and len(dp) > 1:
        # try data-only (e.g. B=16 on a (2,16,16) mesh)
        s = sanitize(P(dp[-1], *([None] * (len(shape) - 1))), shape, mesh)
    return s


def batch_shardings(batch, mesh: Mesh):
    return jax.tree.map(
        lambda a: NamedSharding(mesh, batch_pspec(mesh, a.shape)), batch)


def decode_state_pspec(path, shape, mesh: Mesh, *,
                       kv_shardable: bool = True,
                       batch_shardable: bool = True,
                       slot_absorb: bool = True,
                       model_axis: str = "model") -> P:
    """Sharding for DecodeState leaves (stacked or per-layer caches).

    When KV heads don't divide the model axis (GQA kv=8 on a 16-way axis,
    MQA, MHA with odd head counts) the cache's *slot/sequence* axis takes
    the model axis instead (flash-decode style context parallelism); when
    the batch doesn't divide pod×data (long_500k B=1) the slot axis absorbs
    the data axes too.

    ``slot_absorb=False`` disables that absorption: the slot axis (and the
    trailing dim axis) stay whole per shard, replicating the unshardable
    axis instead. The serving engine uses this when the AQUA block-sparse
    kernels serve the state shard_mapped — the kernels stream full
    dim-major K̂ sequence stripes per (lane, head) shard, so a slot-sharded
    (or dim-block-splitting) layout would force a gather at the shard_map
    boundary every step.
    """
    name = path_str(path).split("/")[-1]
    nd = len(shape)
    dp = data_axes(mesh)
    base = {
        "k": 4, "v": 4, "positions": 2, "count": 1, "acc_score": 3,
        "conv": 3, "state": 2,
    }.get(name)
    batch_ax = dp if batch_shardable else None
    kv_ax = model_axis if kv_shardable else None
    # paged-cache leaves: the page *pool* is global across lanes (any lane
    # may map any page), so it never shards over the data axes — KV heads
    # (whole pages riding with each head) shard over `model`, page
    # tables ride the lane/batch axis, positions replicate (tiny).
    paged = {"k_pool": 4, "v_pool": 4, "acc_pool": 3, "pos_pool": 2,
             "page_table": 2, "k_scale": 2, "v_scale": 2,
             "k_hot": 4, "v_hot": 4, "hot_ids": 1}.get(name)
    if paged is not None:
        pad = [None] * (nd - paged)
        if name in ("k_pool", "v_pool", "k_hot", "v_hot"):
            # pools ((L,) P, KV, ps, D); hot overlay ((L,) H, KV, ps, D)
            spec = P(*pad, None, kv_ax, None, None)
        elif name == "acc_pool":               # ((L,) P, KV, ps)
            spec = P(*pad, None, kv_ax, None)
        elif name == "page_table":             # ((L,) B, NP)
            spec = P(*pad, batch_ax, None)
        elif name in ("k_scale", "v_scale"):   # ((L,) P, SH)
            # per-page quant scales partition with their pages' KV heads
            # over `model` (page axis stays whole, like the pool); the
            # one-scale-per-page granularity (SH=1) sanitizes to
            # replicated.
            spec = P(*pad, None, kv_ax)
        elif name == "hot_ids":                # ((L,) H) — tiny, replicated
            spec = P(*pad, None)
        else:                                  # pos_pool ((L,) P, ps)
            spec = P(*pad, None, None)
        return sanitize(spec, shape, mesh)
    slot_axes = tuple(
        ((() if batch_shardable else dp)
         + (() if kv_shardable else (model_axis,)))
        if slot_absorb else ())
    # canonicalize: bare axis name for singletons (PartitionSpec equality
    # distinguishes "model" from ("model",))
    slot_ax = (slot_axes[0] if len(slot_axes) == 1 else slot_axes) \
        if slot_axes else None
    lead = nd - base if base is not None else 0
    pad = [None] * lead

    def build(*tail):
        return P(*pad, *tail)
    if base is None:
        # extra entries (whisper cross K/V): (L, B, S_enc, KV, D)
        if nd >= 5:
            return sanitize(P(None, batch_ax, None, kv_ax, None), shape, mesh)
        return P(*([None] * nd))
    if name in ("k", "v"):
        spec = build(batch_ax, kv_ax, slot_ax, None)
    elif name == "positions":
        spec = build(batch_ax, slot_ax)
    elif name == "count":
        spec = build(batch_ax)
    elif name == "acc_score":
        spec = build(batch_ax, kv_ax, slot_ax)
    elif name == "conv":
        spec = build(batch_ax, None, model_axis)
    elif name == "state":
        if nd - lead >= 4 or nd >= 4:   # ssm ((L,) B, H, P, N)
            spec = P(*([None] * (nd - 4)), batch_ax, model_axis, None, None)
        else:                           # rglru ((L,) B, W)
            spec = P(*([None] * (nd - 2)), batch_ax, model_axis)
    else:
        spec = P(*([None] * nd))
    return sanitize(spec, shape, mesh)


def make_state_shardings(state, mesh: Mesh, *, kv_heads: int, batch: int,
                         kernel_native: bool = False):
    """``kernel_native=True``: the AQUA block-sparse Pallas kernels will
    serve this state shard_mapped, so the cache layout must keep every
    slot/sequence stripe — and every dim-block of the dim-major K̂ view —
    whole per shard (see ``decode_state_pspec``'s ``slot_absorb``)."""
    model = mesh.shape.get("model", 1)   # data-only meshes: no TP axis
    kv_ok = kv_heads > 0 and kv_heads % model == 0
    b_ok = batch % _axis_size(mesh, data_axes(mesh)) == 0

    def one(path, leaf):
        return NamedSharding(mesh, decode_state_pspec(
            path, leaf.shape, mesh, kv_shardable=kv_ok, batch_shardable=b_ok,
            slot_absorb=not kernel_native))
    return jax.tree_util.tree_map_with_path(one, state)


# The paged decode kernel copies whole pages into (page_size, D) VMEM
# tiles and joins a run of them into one (c·page_size, D) operand; pages
# of whole 8-token sub-blocks (TPU sublane granularity) keep that join
# on tile boundaries.
KERNEL_PAGE_MULTIPLE = 8


def kernel_shardable(mesh: Optional[Mesh], cfg, aqua=None, *,
                     batch: Optional[int] = None,
                     page_size: Optional[int] = None) -> bool:
    """Can the Pallas attention kernels run shard_mapped under ``mesh``?

    Geometry-only predicate (policy checks — H2O, sliding window,
    ``block_dims > 1`` — stay with the dispatch sites in
    ``repro.core.attention`` and ``repro.core.dispatch``):

    * For AQUA-native kernels (``aqua`` given) the kept dims must tile
      into whole ``block_dims`` dim-blocks, so every model shard holds
      whole dim-blocks of the dim-major K̂ cache.
    * A multi-row batch must divide the data axes — lanes partition into
      whole per-data-shard groups (contiguous caches *and* paged page
      tables ride the lane axis). When it doesn't,
      :func:`decode_state_pspec` has already moved the mesh axes onto the
      cache's *slot* axis (context parallelism), and the kernels — which
      stream full sequence stripes per (lane, head) shard — would force a
      gather at the shard_map boundary; those shapes keep the jnp
      reference path. ``batch == 1`` (admission prefills) replicates the
      batch axis instead and stays kernel-runnable, as does MQA's single
      KV head (the head axis replicates).
    * Paged geometry (``page_size`` given): pages must tile into whole
      :data:`KERNEL_PAGE_MULTIPLE`-token sequence blocks. No *sharding*
      divisibility applies to the pool itself: ``model`` only ever
      shards the pool's KV-head axis (dim-blocks and pages ride whole
      per model shard), and the pool never splits over the data axes —
      any lane may map any physical page, so page-table entries are
      pool-global ids valid unchanged on every data shard (see
      :func:`decode_state_pspec`'s paged branch).
    """
    if mesh is None:
        return False
    if aqua is not None:
        if not aqua.enabled or aqua.block_dims < 1:
            return False
        if aqua.kept_dims(cfg.head_dim) % aqua.block_dims != 0:
            return False
    if batch is not None and batch > 1:
        if batch % _axis_size(mesh, data_axes(mesh)) != 0:
            return False
    if page_size is not None and page_size % KERNEL_PAGE_MULTIPLE != 0:
        return False
    return True


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Serving lane rules (continuous-batching engine).
#
# A decode *lane* is one batch row of the shared decode state; the engine's
# per-lane vectors (LaneState fields, sampled tokens, stop flags) are (L,)
# arrays whose axis is the same batch axis the decode caches carry — so both
# shard over the data axes together, keeping the jitted sample-in-step
# decode data-parallel end to end (no gather between the model step and the
# per-lane sampler).
# ---------------------------------------------------------------------------


def lane_pspec(mesh: Mesh, num_lanes: int) -> P:
    """(L,) per-lane vectors: shard over pod×data when divisible."""
    dp = data_axes(mesh)
    if not dp:
        return P(None)
    return sanitize(P(dp), (num_lanes,), mesh)


def page_rank_pspec(mesh: Mesh, batch: int) -> P:
    """(B, KP) hierarchical participating-page tables: lane-partitioned
    over pod×data exactly like ``page_table`` rows (the entries are
    logical per-lane page indices, meaningless across lanes), table
    width whole per shard."""
    dp = data_axes(mesh)
    if not dp:
        return P(None, None)
    return sanitize(P(dp, None), (batch, 1), mesh)


def make_lane_shardings(tree, mesh: Mesh):
    """NamedShardings for a pytree of (L,) / (L, ...) per-lane leaves
    (leading axis = lane). Non-lane trailing dims stay replicated."""
    def one(leaf):
        spec = lane_pspec(mesh, leaf.shape[0])
        return NamedSharding(mesh, P(spec[0], *([None] * (len(leaf.shape)
                                                          - 1))))
    return jax.tree.map(one, tree)


# ---------------------------------------------------------------------------
# Megatron-style sequence-parallel activation constraint.
#
# The launcher installs a NamedSharding for (B, S, D) activations with the
# *sequence* dim sharded over the model axis; models call ``constrain_seq``
# on their scan carries. Effect: the per-layer activations saved by the
# remat-scan for backward are S-sharded (L × B·S·D/16 instead of L × B·S·D
# per device) and the TP output all-reduces become reduce-scatters. Without
# this, pixtral-12b train_4k peaks at 56 GB/device (EXPERIMENTS.md §Perf).
# ---------------------------------------------------------------------------

_ACTIVATION_SHARDING = None  # Optional[NamedSharding] for (B, S, D)


def set_activation_sharding(sharding) -> None:
    global _ACTIVATION_SHARDING
    _ACTIVATION_SHARDING = sharding


def make_seq_parallel_sharding(mesh: Mesh, batch: int, seq: int):
    dp = data_axes(mesh)
    spec = sanitize(P(dp, "model", None), (batch, seq, 1 << 30), mesh)
    return NamedSharding(mesh, spec)


def constrain_seq(x):
    """Apply the installed sequence-parallel constraint to a (B, S, D)
    activation; identity when not configured (CPU tests, decode)."""
    if _ACTIVATION_SHARDING is None or x.ndim != 3:
        return x
    return jax.lax.with_sharding_constraint(x, _ACTIVATION_SHARDING)


# (B, S, W) LRU-width-sharded constraint for the RG-LRU gate outputs: with
# the gate output constrained to the same W-sharding as its input, GSPMD
# all-gathers the bf16 input once instead of all-reducing the f32 partial
# outputs of the contraction-sharded W×W matmul (4x less ICI traffic).
_LRU_GATE_SHARDING = None


def set_lru_gate_sharding(sharding) -> None:
    global _LRU_GATE_SHARDING
    _LRU_GATE_SHARDING = sharding


def make_width_sharding(mesh: Mesh, batch: int, width: int):
    dp = data_axes(mesh)
    spec = sanitize(P(dp, None, "model"), (batch, 1 << 30, width), mesh)
    return NamedSharding(mesh, spec)


def constrain_lru_gate(x):
    if _LRU_GATE_SHARDING is None or x.ndim != 3:
        return x
    return jax.lax.with_sharding_constraint(x, _LRU_GATE_SHARDING)
