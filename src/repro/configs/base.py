"""Configuration dataclasses for the repro framework.

Every assigned architecture is expressed as a ``ModelConfig``; AQUA is a
first-class, orthogonal ``AquaConfig`` attached to any attention-bearing
model. Configs are plain frozen dataclasses so they hash/compare cleanly
and can be used as jit static args.
"""
from __future__ import annotations

import math
import warnings
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple


@dataclass(frozen=True)
class AquaConfig:
    """Paper hyperparameters (§8.1, §8.4) plus TPU-adaptation knobs."""

    enabled: bool = True
    # Fraction of (remaining) dims kept for the score dot-product (paper k_ratio).
    k_ratio: float = 0.75
    # AQUA-Memory static slice: fraction of trailing principal dims dropped
    # before caching (paper S_ratio). 0.0 disables AQUA-Memory.
    s_ratio: float = 0.0
    # H2O heavy-hitter cache budget as a fraction of full context
    # (paper H2O_ratio). 1.0 disables eviction.
    h2o_ratio: float = 1.0
    # Fraction of the H2O budget reserved for the most recent tokens.
    h2o_recent_frac: float = 0.5
    # TPU adaptation: magnitude selection granularity in dims. 1 = exact
    # paper semantics (per-dim); 8 = sublane-block granularity used by the
    # Pallas kernel's scalar-prefetch DMA path.
    block_dims: int = 1
    # Fold P into W_Q / W_K offline when legal (no per-step projection cost).
    fold_projection: bool = True
    # Block-sparse kernel tile sizes (repro.kernels.aqua_prefill/aqua_decode):
    # queries per prefill chunk (one dim-block selection per chunk), keys per
    # prefill tile, and keys per decode seq-block. Threaded through the
    # attention backend registry (repro.core.attention).
    prefill_q_blk: int = 128
    prefill_k_blk: int = 128
    decode_seq_blk: int = 128

    @property
    def e_ratio(self) -> float:
        """Paper's effective ratio for AQUA-Memory."""
        return (1.0 - self.s_ratio) * self.k_ratio

    def kept_dims(self, head_dim: int) -> int:
        """Dims retained after the static slice (AQUA-Memory stage 1)."""
        d = int(round((1.0 - self.s_ratio) * head_dim))
        return max(self.block_dims, min(head_dim, d))

    def topk_dims(self, head_dim: int) -> int:
        """Dims kept by dynamic magnitude selection (stage 2)."""
        d_kept = self.kept_dims(head_dim)
        k = int(round(self.k_ratio * d_kept))
        k = max(self.block_dims, min(d_kept, k))
        # round up to selection granularity
        b = self.block_dims
        return ((k + b - 1) // b) * b


@dataclass(frozen=True)
class AttentionConfig:
    num_heads: int
    num_kv_heads: int
    head_dim: int
    kind: str = "full"           # full | swa (sliding-window) | local
    window: Optional[int] = None  # for swa/local
    qk_norm: bool = False         # qwen3-style per-head RMSNorm on q,k
    qkv_bias: bool = False        # qwen1.5-style bias on q,k,v projections
    rope_theta: float = 10000.0
    use_rope: bool = True         # False -> absolute learned positions (whisper)
    causal: bool = True           # False for encoder self-attention
    # Attention backend registry key (repro.core.attention): "auto" |
    # "dense-jnp" | "flash" | "aqua-masked-dense" | "aqua-block-sparse".
    # "auto" picks Pallas kernels on TPU and jnp references elsewhere;
    # explicit kernel backends fall back to the masked-dense reference when
    # Pallas is unavailable.
    backend: str = "auto"

    @property
    def group_size(self) -> int:
        assert self.num_heads % self.num_kv_heads == 0
        return self.num_heads // self.num_kv_heads


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    expert_ff: int
    num_shared: int = 0          # qwen2-moe shared experts
    router_aux_weight: float = 0.01
    router_jitter: float = 0.0
    capacity_factor: float = 1.25


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 SSD block parameters."""

    state_dim: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk_size: int = 64
    ngroups: int = 1


@dataclass(frozen=True)
class RGLRUConfig:
    """RecurrentGemma RG-LRU block parameters."""

    lru_width: int = 0            # 0 -> d_model
    conv_width: int = 4
    block_pattern: Tuple[str, ...] = ("recurrent", "recurrent", "attention")


@dataclass(frozen=True)
class FrontendConfig:
    """Stub modality frontends (audio frames / vision patches).

    ``input_specs`` provides precomputed embeddings of shape
    (batch, num_embeds, embed_dim); the model projects and splices them.
    """

    kind: str = "none"            # none | audio_frames | vision_patches
    num_embeds: int = 0
    embed_dim: int = 0


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    attention: Optional[AttentionConfig] = None
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    frontend: FrontendConfig = field(default_factory=FrontendConfig)
    aqua: Optional[AquaConfig] = None
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # encoder-decoder (whisper): encoder depth; decoder uses num_layers.
    num_encoder_layers: int = 0
    act: str = "silu"             # silu | gelu
    max_positions: int = 32768    # learned-position table size (use_rope=False)
    dtype: str = "bfloat16"       # activation/compute dtype
    param_dtype: str = "float32"
    remat: bool = True            # activation checkpointing per block
    # long-context capability flag (drives shape applicability):
    # sub-quadratic if SSM/hybrid or windowed attention.
    skip_long_context: bool = False

    @property
    def subquadratic(self) -> bool:
        if self.family in ("ssm", "hybrid"):
            return True
        if self.attention is not None and self.attention.kind in ("swa", "local"):
            return True
        return False

    def with_aqua(self, aqua: AquaConfig) -> "ModelConfig":
        return replace(self, aqua=aqua)

    def validate(self) -> None:
        assert self.family in ("dense", "moe", "ssm", "hybrid", "encdec", "vlm")
        if self.family != "ssm":
            assert self.attention is not None
        if self.family == "moe":
            assert self.moe is not None
        if self.family == "hybrid":
            assert self.rglru is not None
        if self.family == "encdec":
            assert self.num_encoder_layers > 0


def reduce_config(cfg: ModelConfig, *, layers: int = 2, d_model: int = 64,
                  vocab: int = 128, ff: int = 128) -> ModelConfig:
    """Shrink a production config to a CPU-smoke-testable size, preserving
    every structural feature (GQA ratio, qk_norm, MoE routing, SWA, ...)."""
    kw: dict = dict(num_layers=layers, d_model=d_model, vocab_size=vocab, d_ff=ff)
    if cfg.attention is not None:
        heads = max(2, min(4, cfg.attention.num_heads))
        # preserve GQA-ness: kv < heads iff original had grouping
        kv = heads if cfg.attention.num_kv_heads == cfg.attention.num_heads else max(1, heads // 2)
        kw["attention"] = replace(
            cfg.attention, num_heads=heads, num_kv_heads=kv,
            head_dim=max(8, d_model // heads),
            window=None if cfg.attention.window is None else 16)
    if cfg.moe is not None:
        kw["moe"] = replace(cfg.moe, num_experts=8,
                            top_k=min(2, cfg.moe.top_k), expert_ff=ff // 2,
                            num_shared=min(1, cfg.moe.num_shared),
                            capacity_factor=8.0)  # effectively dropless
    if cfg.ssm is not None:
        kw["ssm"] = replace(cfg.ssm, state_dim=16, head_dim=16, chunk_size=8)
    if cfg.rglru is not None:
        kw["rglru"] = replace(cfg.rglru, lru_width=0)
    if cfg.frontend.kind != "none":
        kw["frontend"] = replace(cfg.frontend, num_embeds=4, embed_dim=32)
    if cfg.num_encoder_layers:
        kw["num_encoder_layers"] = 2
    kw["remat"] = False
    kw["dtype"] = "float32"
    return replace(cfg, **kw)


@dataclass(frozen=True)
class CacheSpec:
    """KV-cache layout: the one non-deprecated way to configure the
    serving cache (resolved once at engine construction, like
    ``core.dispatch.DispatchPlan``).

    ``page_size`` tokens per page turns the per-lane contiguous slot
    stripes into a global page pool with per-lane page tables
    (``repro.core.kvcache.PagedAttnCache``); None keeps the contiguous
    layout. ``num_pages`` sizes the pool (None = lane-stripe parity:
    ``max_lanes * slots / page_size``) — set it lower to realize the
    memory win (admissions queue when the pool is full).
    ``prefix_sharing`` maps identical page-aligned prompt prefixes into
    multiple lanes (refcounted, copy-on-write; paged full-cache policy
    only). ``eviction`` names the slot-eviction policy; ``"auto"``
    derives it from the model config (H2O when ``AquaConfig.h2o_ratio``
    < 1, ring when the attention is windowed, none otherwise) — the
    explicit names exist for config introspection and forward-compat,
    the engine rejects a name that contradicts the model policy.
    """

    page_size: Optional[int] = None
    num_pages: Optional[int] = None
    prefix_sharing: bool = True
    eviction: str = "auto"        # auto | none | ring | h2o

    @property
    def paged(self) -> bool:
        return self.page_size is not None

    def validate(self) -> None:
        assert self.eviction in ("auto", "none", "ring", "h2o"), self.eviction
        if self.page_size is not None:
            assert self.page_size >= 1
            if self.num_pages is not None:
                assert self.num_pages >= 1
        elif self.num_pages is not None:
            raise ValueError("CacheSpec.num_pages needs page_size (paged "
                             "layout only)")


@dataclass(frozen=True)
class QuantSpec:
    """KV-pool quantization (paged layout only).

    ``kv_dtype``: pool storage dtype — ``"bf16"`` keeps full-precision
    pools, ``"int8"`` stores per-page symmetric-quantized K̂/V with f32
    scales living beside the page table (zero-point 0; the Pallas decode
    kernel scalar-prefetches them for dequant-free, scale-folded score
    accumulation).
    ``scale_granularity``: ``"page_head"`` keeps one scale per
    (page, kv-head); ``"page"`` shares one scale across a page's heads
    (half the metadata, coarser clipping).
    ``hot_resident_fraction``: fraction of the pool kept as
    full-precision *hot residents* — pages with the highest H2O
    accumulated scores carry a write-through bf16 overlay beside their
    (always-written) int8 twin, and readers prefer the overlay. 0
    disables mixed precision (every page reads quantized).
    """

    kv_dtype: str = "bf16"              # bf16 | int8
    scale_granularity: str = "page_head"  # page_head | page
    hot_resident_fraction: float = 0.0

    @property
    def quantized(self) -> bool:
        return self.kv_dtype != "bf16"

    @property
    def mode(self) -> str:
        """Dispatch-plan label: none | int8 | int8-mixed."""
        if not self.quantized:
            return "none"
        return (f"{self.kv_dtype}-mixed" if self.hot_resident_fraction > 0
                else self.kv_dtype)

    def validate(self) -> None:
        assert self.kv_dtype in ("bf16", "int8"), self.kv_dtype
        assert self.scale_granularity in ("page_head", "page"), \
            self.scale_granularity
        assert 0.0 <= self.hot_resident_fraction <= 1.0, \
            self.hot_resident_fraction


@dataclass(frozen=True)
class SparsitySpec:
    """Two-stage hierarchical sparsity (paged layout only).

    Sibling of :class:`CacheSpec`/:class:`QuantSpec` — the third leg of
    the unified serving-config surface, resolved once at engine
    construction (:func:`resolve_sparsity_spec`).

    **Stage 1 (token sparsity, page-granular):** each decode step ranks a
    lane's mapped pages by their H2O accumulated attention mass
    (``PagedAttnCache.acc_pool`` — the statistic the pool already
    maintains, a free block-ranking signal where HyperAttention uses LSH)
    and only the top ``page_keep_ratio`` fraction *participates* in
    attention at all; the last ``pin_recent_pages`` pages of the lane
    (the tail holding the probe token and the local window) are always
    kept, so recency is exact. Pages with no accumulated mass tie at
    zero and resolve to the lowest page indices — the selection then
    degrades gracefully to attention-sink + recent-tail behavior.

    **Stage 2 (dim sparsity):** AQUA's per-query |q̂| dim-block top-k,
    unchanged, applied only within participating pages.

    The participating-page set is composed into the Pallas decode
    kernel's per-lane page list, so non-participating pages cost zero HBM
    bytes — decode compute and
    bandwidth scale with ``kept_pages``, not context length.
    ``page_keep_ratio=1.0`` disables stage 1 (bit-identical to the plain
    paged kernel: the participation table is the identity map).
    """

    page_keep_ratio: float = 1.0
    # Recency pin: the trailing pages of each lane (by token count) are
    # always in the participating set, independent of their scores.
    pin_recent_pages: int = 2

    @property
    def hierarchical(self) -> bool:
        return self.page_keep_ratio < 1.0

    def kept_pages(self, pages_per_lane: int) -> int:
        """Static participating-set size for a lane of
        ``pages_per_lane`` logical pages (the kernel grid extent)."""
        k = math.ceil(self.page_keep_ratio * pages_per_lane - 1e-9)
        k = max(k, min(self.pin_recent_pages, pages_per_lane), 1)
        return min(k, pages_per_lane)

    def validate(self) -> None:
        assert 0.0 < self.page_keep_ratio <= 1.0, self.page_keep_ratio
        assert self.pin_recent_pages >= 1, self.pin_recent_pages


def resolve_sparsity_spec(serving: "ServingConfig") -> "SparsitySpec":
    """Resolve a ``ServingConfig``'s token-sparsity surface — the
    :class:`SparsitySpec` twin of :func:`resolve_cache_specs` (no legacy
    flat fields to shim; hierarchical mode cross-validates against the
    cache layout the same way quantization does)."""
    spec = serving.sparsity if serving.sparsity is not None else SparsitySpec()
    spec.validate()
    if spec.hierarchical:
        cache, _ = resolve_cache_specs(serving, warn=False)
        if not cache.paged:
            raise ValueError(
                f"SparsitySpec(page_keep_ratio={spec.page_keep_ratio}) "
                "needs the paged cache layout — stage-1 selection is "
                "page-granular; set CacheSpec.page_size")
    return spec


# ServingConfig fields shadowed by CacheSpec: (flat name, CacheSpec name,
# deprecated-iff-not-this default). One-release DeprecationWarning shims
# (the kernel_native shim pattern from PR 6, removed in PR 7).
_LEGACY_CACHE_FIELDS = (("page_size", "page_size", None),
                        ("num_pages", "num_pages", None),
                        ("prefix_sharing", "prefix_sharing", True))


def resolve_cache_specs(serving: "ServingConfig", *, warn: bool = True
                        ) -> Tuple[CacheSpec, QuantSpec]:
    """Resolve a ``ServingConfig``'s cache surface to (CacheSpec,
    QuantSpec) — the single resolution point, called once per engine
    (``warn=True``) and silently by ``validate()``/dispatch resolution
    (``warn=False``).

    The old flat fields (``page_size``/``num_pages``/``prefix_sharing``)
    are one-release deprecated shims: set them and a DeprecationWarning
    names the replacement; set them *and* ``cache=`` and resolution
    fails loudly instead of silently preferring one side.
    """
    legacy = [flat for flat, _, default in _LEGACY_CACHE_FIELDS
              if getattr(serving, flat) != default]
    if legacy:
        if serving.cache is not None:
            raise ValueError(
                f"ServingConfig sets both cache=CacheSpec(...) and the "
                f"deprecated flat field(s) {legacy} — move the flat "
                "values into the CacheSpec")
        if warn:
            warnings.warn(
                f"ServingConfig.{'/'.join(legacy)} are deprecated; pass "
                "cache=CacheSpec(page_size=..., num_pages=..., "
                "prefix_sharing=...) instead (one-release shim)",
                DeprecationWarning, stacklevel=3)
    if serving.cache is not None:
        cache = serving.cache
    else:
        cache = CacheSpec(page_size=serving.page_size,
                          num_pages=serving.num_pages,
                          prefix_sharing=serving.prefix_sharing)
    quant = serving.quant if serving.quant is not None else QuantSpec()
    cache.validate()
    quant.validate()
    if quant.quantized and not cache.paged:
        raise ValueError(
            f"QuantSpec(kv_dtype={quant.kv_dtype!r}) needs the paged "
            "cache layout — quantization state is per-page metadata; "
            "set CacheSpec.page_size")
    return cache, quant


@dataclass(frozen=True)
class ServingConfig:
    """Continuous-batching engine knobs (repro.serving).

    A *lane* is one batch row of the shared decode state. Requests are
    admitted into free lanes and retired independently, so the decode
    step always runs at the static shape ``(max_lanes,)`` — jit compiles
    exactly once regardless of traffic.
    """

    max_lanes: int = 8
    max_seq: int = 4096
    # per-request defaults (overridable per Request)
    max_new_tokens: int = 128
    temperature: float = 0.0
    top_k: int = 0               # 0 disables top-k filtering
    eos_id: int = -1             # -1 disables EOS stop detection
    pad_id: int = 0              # token reported for inactive lanes
    # Prompts are right-padded to a multiple of this bucket and prefilled
    # with ragged ``lengths`` so prefill compiles once per bucket, not once
    # per prompt length. Policies that reject ragged prefill (sliding
    # window, H2O eviction) fall back to exact-length prefill.
    prompt_bucket: int = 16
    # Device mesh for mesh-native serving: ``mesh_shape`` (e.g. (4, 2))
    # over ``mesh_axes`` (data × model). None serves single-device. Decode
    # lanes are data-parallel over the data axes; params and the KV cache
    # (including AQUA dim-sliced key lanes and H2O acc_score) shard over
    # the model axis per distributed.sharding's name+shape rules.
    mesh_shape: Optional[Tuple[int, ...]] = None
    mesh_axes: Tuple[str, ...] = ("data", "model")
    # DEPRECATED flat cache fields (one-release shims): use
    # ``cache=CacheSpec(page_size=..., num_pages=..., prefix_sharing=...)``
    # instead. Setting any of them emits a DeprecationWarning at engine
    # construction; setting them alongside ``cache=`` is an error (see
    # :func:`resolve_cache_specs`).
    page_size: Optional[int] = None
    num_pages: Optional[int] = None
    prefix_sharing: bool = True
    # Chunked-prefill/decode interleaving: cap the prefill tokens advanced
    # per decode step. Prompts longer than the budget are admitted
    # immediately into a PREFILLING lane and their KV cache is built in
    # page-aligned chunks between decode steps, so active decode lanes
    # never stall longer than one chunk. None keeps monolithic admission
    # (the whole prefill runs inside the admit). Must be a multiple of
    # ``prompt_bucket`` (and of ``page_size`` when paged) so every
    # non-final chunk lands bucket- and page-aligned.
    prefill_budget_tokens: Optional[int] = None
    # Admission head-of-line lookahead: number of queue positions tried
    # first-fit per admission pass when the head cannot reserve pages —
    # the head plus up to ``admission_lookahead - 1`` later *arrived*
    # requests. 1 = strict FIFO (head-only, the pre-lookahead behavior).
    # Skipped-over requests keep their exact queue position.
    admission_lookahead: int = 4
    # The unified cache-configuration surface (the only non-deprecated
    # one): layout/geometry in ``cache``, pool quantization in ``quant``.
    # None means defaults (contiguous layout, bf16 pools) — or, one
    # release longer, whatever the deprecated flat fields above say.
    cache: Optional[CacheSpec] = None
    quant: Optional[QuantSpec] = None
    # Two-stage hierarchical sparsity (page-granular token sparsity ×
    # AQUA dim-block sparsity). None means SparsitySpec() defaults: every
    # page participates (no token sparsity).
    sparsity: Optional[SparsitySpec] = None

    def validate(self) -> None:
        assert self.max_lanes >= 1
        assert self.max_new_tokens >= 1
        assert self.prompt_bucket >= 1
        assert self.admission_lookahead >= 1
        cache, _ = resolve_cache_specs(self, warn=False)
        resolve_sparsity_spec(self)
        if self.prefill_budget_tokens is not None:
            assert self.prefill_budget_tokens >= 1
            assert self.prefill_budget_tokens % self.prompt_bucket == 0, \
                (self.prefill_budget_tokens, self.prompt_bucket)
            if cache.page_size is not None:
                assert self.prefill_budget_tokens % cache.page_size == 0, \
                    (self.prefill_budget_tokens, cache.page_size)
        if cache.page_size is not None:
            assert self.max_seq % cache.page_size == 0, \
                (self.max_seq, cache.page_size)
        if self.mesh_shape is not None:
            assert len(self.mesh_shape) == len(self.mesh_axes), \
                (self.mesh_shape, self.mesh_axes)
            assert all(s >= 1 for s in self.mesh_shape), self.mesh_shape
            assert all(a in ("pod", "data", "model")
                       for a in self.mesh_axes), self.mesh_axes


@dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    mode: str                      # train | prefill | decode

    @property
    def is_serve(self) -> bool:
        return self.mode in ("prefill", "decode")


SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", 4096, 256, "train"),
    ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    ShapeConfig("decode_32k", 32768, 128, "decode"),
    ShapeConfig("long_500k", 524288, 1, "decode"),
)

SHAPES_BY_NAME = {s.name: s for s in SHAPES}


@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    microbatches: int = 1          # gradient accumulation
    grad_compress: bool = False    # int8 error-feedback allreduce
    seed: int = 0
    checkpoint_every: int = 100
    keep_checkpoints: int = 3
