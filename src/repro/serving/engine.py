"""Serving engines: rectangular batch (``ServeEngine``) and continuous
batching (``ContinuousBatchingEngine``).

``ServeEngine`` keeps the original calibrate-once/serve API: one
rectangular prompt batch prefills together and decodes in lockstep for a
fixed number of steps. Sampling (greedy/temperature) and the RNG fold
now live *inside* the jitted decode step — the host loop never splits
keys or touches logits, so each step is a single device dispatch.

``ContinuousBatchingEngine`` is the production-shaped stack: requests
are admitted into fixed decode *lanes* (batch rows of one shared decode
state), each lane prefills independently (ragged, bucketed prompt
shapes) and its cache — including H2O ``acc_score`` and AQUA dim-sliced
key lanes — is grafted into the occupied batch via the model's lane
surgery API (``LM.prefill_into`` / ``insert_lane``). The decode step is
fully jitted at the static ``(max_lanes,)`` shape and folds in
per-request sampling (greedy / temperature / top-k, RNG derived by
``fold_in`` on the request uid and token counter so results are
independent of lane placement and co-tenants) plus EOS/length stop
detection; inactive lanes ride along under a ``write_mask`` that freezes
their cache. The host loop only drains finished lanes and streams
per-request tokens.

Attention backend: both engines flow through the backend registry in
``repro.core.attention`` (selected by ``cfg.attention.backend``,
overridable per-engine via the ``backend`` constructor argument).

Mesh-native serving: pass ``mesh=`` (or set ``ServingConfig.mesh_shape``)
and the continuous-batching engine runs the whole serve loop under an
explicit data×model mesh — params and the KV cache (AQUA dim-sliced key
lanes, H2O ``acc_score``) shard over ``model`` per
``distributed.sharding``'s rules, decode lanes shard over the data axes,
the attention cores run under ``shard_map`` — including the AQUA
block-sparse Pallas prefill/decode kernels, which serve shard_mapped
with per-shard block-index tables whenever the axis extents divide the
mesh (``distributed.sharding.kernel_shardable``) — and the lane-surgery
admission path preserves shardings end to end (every jitted entry point
is pinned with ``out_shardings``). Single-device behavior is untouched
when no mesh is configured.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from dataclasses import dataclass
from typing import Dict, Iterable, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (ModelConfig, ServingConfig,
                                resolve_cache_specs, resolve_sparsity_spec)
from repro.core import kvcache as kvc
from repro.core.calibration import AquaProjections
from repro.core.dispatch import DispatchPlan, resolve_dispatch_plan
from repro.core.h2o import h2o_budget
from repro.models import build_model
from repro.models.base import DecodeState, PagingSpec
from repro.serving import telemetry
from repro.serving.scheduler import (LaneScheduler, PagePool, Request,
                                     RequestOutput, ScheduleStats,
                                     StreamEvent)

NEG_INF = -1e30


def decode_state_bytes(model, batch_size: int, max_seq: int) -> int:
    """KV-cache footprint of a decode state (shape-only: ``jax.eval_shape``
    traces ``init_decode_state`` abstractly, no device memory is touched).
    The single source of truth for cache-byte accounting — both engines'
    ``cache_bytes`` and the benches report this number. Pool-based layouts
    (paged caches) are counted once, not per lane, so AQUA-Memory *and*
    paged-pool savings both show up here."""
    state = jax.eval_shape(
        lambda: model.init_decode_state(batch_size, max_seq))
    return kvc.tree_bytes(state.layers)


# ---------------------------------------------------------------------------
# Shared sampling (jit-side)
# ---------------------------------------------------------------------------


def sample_tokens(logits: jax.Array, keys: jax.Array,
                  temperature: jax.Array, top_k: jax.Array,
                  use_top_k: bool = True) -> jax.Array:
    """Per-row sampling. logits (N, V); keys (N, ...) PRNG keys;
    temperature (N,) f32; top_k (N,) int32 (0 disables the filter; ties
    at the k-th logit are all kept). temperature <= 0 is greedy.

    ``use_top_k`` is a *static* gate: when the caller knows no row uses
    top-k it compiles the step without the full-vocab sort that the
    dynamic per-row threshold otherwise needs."""
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits.astype(jnp.float32)
    if use_top_k:
        sorted_desc = jnp.sort(lg, axis=-1)[:, ::-1]
        idx = jnp.clip(top_k - 1, 0, v - 1)
        thr = jnp.take_along_axis(sorted_desc, idx[:, None], axis=-1)
        lg = jnp.where((top_k[:, None] <= 0) | (lg >= thr), lg, NEG_INF)
    scaled = lg / jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


def _request_keys(rng: jax.Array, uid: jax.Array,
                  token_index: jax.Array) -> jax.Array:
    """(N,) per-request keys: fold the request uid then the token counter
    into the serve-level base key. Placement/co-tenant independent."""
    return jax.vmap(lambda u, i: jax.random.fold_in(
        jax.random.fold_in(rng, u), i))(uid, token_index)


# ---------------------------------------------------------------------------
# Rectangular-batch engine (kept for scoring, tests, and simple drives)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray          # (B, steps)
    logits_last: np.ndarray


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params,
                 projections: Optional[AquaProjections] = None,
                 max_seq: int = 4096, rng_seed: int = 0,
                 backend: Optional[str] = None):
        if backend is not None and cfg.attention is not None:
            from repro.core.attention import resolve_backend
            # fail fast on unknown names; accepts the "auto" selector
            resolve_backend(backend, aqua=cfg.aqua)
            cfg = dataclasses.replace(
                cfg, attention=dataclasses.replace(cfg.attention,
                                                   backend=backend))
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params
        self.proj = None
        if cfg.aqua is not None and cfg.aqua.enabled:
            assert projections is not None, \
                "AQUA enabled: calibrated projections required"
            self.proj = projections.p
        self.max_seq = max_seq
        self._base_rng = jax.random.PRNGKey(rng_seed)
        self._calls = 0

        self._prefill = jax.jit(
            lambda p, batch, proj: self.model.prefill(p, batch, max_seq,
                                                      aqua_proj=proj))

        def step(p, state, tok, proj, rng, i, temp):
            logits, state = self.model.decode_step(p, state, tok,
                                                   aqua_proj=proj)
            return logits, state, _sample_batch(logits, rng, i, temp)
        self._step = jax.jit(step)
        self._sample0 = jax.jit(_sample_batch)

    def generate(self, batch: Dict[str, jax.Array], steps: int,
                 temperature: float = 0.0) -> GenerationResult:
        """batch: prompt inputs ({"tokens": (B, S_prompt), ...}).

        Sampling runs inside the jitted step: the per-token key is
        ``fold_in(call_key, token_index)`` — no host-side key splitting,
        no host sync beyond draining each step's sampled tokens.
        """
        if "lengths" in batch and self.cfg.family not in ("dense", "vlm",
                                                          "moe"):
            raise ValueError(
                "ragged `lengths` prefill is only supported by the "
                "dense-transformer families (dense/vlm/moe); "
                f"{self.cfg.family!r} prefill is rectangular")
        rng = jax.random.fold_in(self._base_rng, self._calls)
        self._calls += 1
        temp = jnp.float32(temperature)
        logits, state = self._prefill(self.params, batch, self.proj)
        tok = self._sample0(logits, rng, 0, temp)
        out: List[np.ndarray] = [np.asarray(tok)]
        for i in range(1, steps):
            logits, state, tok = self._step(self.params, state, tok,
                                            self.proj, rng, i, temp)
            out.append(np.asarray(tok))
        return GenerationResult(tokens=np.stack(out, axis=1),
                                logits_last=np.asarray(logits))

    # ------------------------------------------------------------------
    def score(self, batch: Dict[str, jax.Array]) -> jax.Array:
        """Teacher-forced mean NLL of ``labels`` under the engine's AQUA
        operating point (used by the perplexity benchmarks)."""
        from repro.models.layers import cross_entropy
        logits = self.model.forward(self.params, batch, aqua_proj=self.proj)
        if isinstance(logits, tuple):
            logits = logits[0]
        return cross_entropy(logits, batch["labels"])

    def cache_bytes(self, batch_size: int) -> int:
        """Actual KV-cache footprint at this operating point (AQUA-Memory
        savings show up here). See :func:`decode_state_bytes`."""
        return decode_state_bytes(self.model, batch_size, self.max_seq)


def _sample_batch(logits: jax.Array, rng: jax.Array, i,
                  temp: jax.Array) -> jax.Array:
    """Rectangular-engine sampling: per-row keys derived from the step
    key (``fold_in`` on the token counter then the row), shared
    implementation with the lane engine (no top-k on this path)."""
    key = jax.random.fold_in(rng, i)
    b = logits.shape[0]
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        key, jnp.arange(b, dtype=jnp.int32))
    return sample_tokens(logits, keys, jnp.full((b,), temp, jnp.float32),
                         jnp.zeros((b,), jnp.int32), use_top_k=False)


# ---------------------------------------------------------------------------
# Continuous-batching engine
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclass
class LaneState:
    """Per-lane device bookkeeping folded into the jitted step."""

    last_token: jax.Array   # (L,) int32 — token fed to the next decode step
    active: jax.Array       # (L,) bool
    generated: jax.Array    # (L,) int32 — tokens emitted (incl. prefill's)
    max_new: jax.Array      # (L,) int32
    temperature: jax.Array  # (L,) f32
    top_k: jax.Array        # (L,) int32 — 0 disables
    eos_id: jax.Array       # (L,) int32 — -1 disables
    uid: jax.Array          # (L,) int32 — request uid (RNG fold key)


def _init_lane_state(num_lanes: int) -> LaneState:
    z = jnp.zeros((num_lanes,), jnp.int32)
    return LaneState(last_token=z, active=jnp.zeros((num_lanes,), bool),
                     generated=z, max_new=z,
                     temperature=jnp.zeros((num_lanes,), jnp.float32),
                     top_k=z, eos_id=z - 1, uid=z - 1)


class ContinuousBatchingEngine:
    """Continuous-batching serve stack (see the module docstring).

    Typical drive::

        eng = ContinuousBatchingEngine(cfg, params, proj,
                                       serving=ServingConfig(max_lanes=4))
        for ev in eng.serve(requests):        # StreamEvent per token
            print(ev.uid, ev.token, ev.finished)
        print(eng.stats.mean_occupancy)

    or collect terminal outputs with ``run(requests)``.

    Compilation: the decode step compiles once (static lane shape); the
    admission path compiles once per prompt *bucket* (prompts are padded
    to ``ServingConfig.prompt_bucket`` multiples and prefilled ragged via
    ``lengths`` wherever the cache policy permits — sliding-window and
    H2O policies prefill at exact prompt length instead, which costs one
    compile per distinct length).
    """

    def __init__(self, cfg: ModelConfig, params,
                 projections: Optional[AquaProjections] = None,
                 serving: ServingConfig = ServingConfig(),
                 rng_seed: int = 0, backend: Optional[str] = None,
                 mesh=None):
        if backend is not None and cfg.attention is not None:
            from repro.core.attention import resolve_backend
            resolve_backend(backend, aqua=cfg.aqua)
            cfg = dataclasses.replace(
                cfg, attention=dataclasses.replace(cfg.attention,
                                                   backend=backend))
        serving.validate()
        self.cfg = cfg
        self.scfg = serving
        # the one resolution point of the cache/quant config surface:
        # flat legacy fields warn here (once per engine), everywhere else
        # resolves silently against the same specs
        self.cache_spec, self.quant_spec = resolve_cache_specs(serving,
                                                               warn=True)
        self.sparsity_spec = resolve_sparsity_spec(serving)
        self.model = build_model(cfg)
        self.params = params
        self.proj = None
        if cfg.aqua is not None and cfg.aqua.enabled:
            assert projections is not None, \
                "AQUA enabled: calibrated projections required"
            self.proj = projections.p
        self._base_rng = jax.random.PRNGKey(rng_seed)
        self._serves = 0
        self.stats = ScheduleStats()

        # ragged bucketed prefill needs the contiguous full-cache policy
        # (window rings and H2O eviction place slots rectangularly)
        self._supports_ragged = (
            cfg.family in ("dense", "vlm", "moe")
            and (cfg.attention is None or cfg.attention.window is None)
            and h2o_budget(cfg.aqua, serving.max_seq) is None)

        # block-paged KV cache: a global page pool + per-lane page tables
        # replaces the contiguous per-lane slot stripes; the host-side
        # PagePool allocator (created per drive in serve()) hands finished
        # page-table rows to the jitted admission steps
        cache_spec, quant_spec = self.cache_spec, self.quant_spec
        self._paged = cache_spec.paged
        self.page_pool: Optional[PagePool] = None
        if self._paged:
            if cfg.attention is None or not self.model.supports_paging:
                raise ValueError(
                    f"family {cfg.family!r} does not support the paged "
                    "KV cache")
            from repro.core.kvcache import cache_slots
            slots = cache_slots(serving.max_seq, cfg.attention.window,
                                h2o_budget(cfg.aqua, serving.max_seq))
            if slots % cache_spec.page_size != 0:
                raise ValueError(
                    f"cache slots ({slots}: window/H2O budget) must be a "
                    f"multiple of page_size={cache_spec.page_size} so the "
                    "ring/eviction slot arithmetic tiles into whole pages")
            self._pages_per_lane = slots // cache_spec.page_size
            self._num_slots = slots
            num_pages = cache_spec.num_pages
            if num_pages is None:       # lane-stripe parity by default
                num_pages = serving.max_lanes * self._pages_per_lane
            # hot residents: a fraction of the pool carries the
            # full-precision write-through overlay (mixed precision)
            hot_pages = 0
            if quant_spec.quantized and quant_spec.hot_resident_fraction:
                hot_pages = max(
                    1, int(round(quant_spec.hot_resident_fraction
                                 * num_pages)))
            self.model.enable_paging(PagingSpec(
                cache_spec.page_size, num_pages,
                kv_dtype=quant_spec.kv_dtype,
                scale_granularity=quant_spec.scale_granularity,
                hot_pages=hot_pages))
            self._num_pages = num_pages
            # prefix sharing: identical page-aligned prompt prefixes map
            # the same physical pages. Needs position-pure token K/V
            # (causal, no modality frontend splice) and the full-cache
            # policy (shared pages are read-only; H2O statistics and ring
            # overwrites would write them)
            self._prefix_ok = (cache_spec.prefix_sharing
                               and self._supports_ragged
                               and cfg.frontend.kind == "none")
        else:
            self._prefix_ok = False

        # mesh-native serving: an explicit mesh (or ServingConfig.mesh_shape)
        # shards params + decode caches over `model` and decode lanes over
        # the data axes; every jitted entry point is pinned to those
        # shardings so the serve loop never reshards or bounces device state
        # through the host
        self.mesh = mesh
        if self.mesh is None and serving.mesh_shape is not None:
            from repro.launch.mesh import make_serving_mesh
            self.mesh = make_serving_mesh(serving.mesh_shape,
                                          serving.mesh_axes)
        self._lane_order = None
        # the engine's single resolved dispatch decision: backend, cache
        # layout, mesh-nativeness, and structured fallback reasons. The
        # plan is resolved from the same predicates the attention product
        # applies at trace time, so ``dispatch_plan().mesh_native`` iff
        # the mesh_fallback_events() record stays empty
        self._plan: DispatchPlan = resolve_dispatch_plan(
            attention=cfg.attention, aqua=cfg.aqua, serving=serving,
            mesh=self.mesh, prefix_sharing=self._prefix_ok,
            family=cfg.family, frontend=cfg.frontend.kind)
        self._kernel_native = self._plan.mesh_native
        # hierarchical token sparsity: resolve the per-lane participating
        # page count once (SparsitySpec is static config; the *table* is
        # per-step). None = every page participates — either the config
        # keeps everything or the plan vetoed it (REASON_TOKEN_*).
        self._kept_pages = None
        if self._paged and self._plan.token_sparsity == "hierarchical":
            kp = self.sparsity_spec.kept_pages(self._pages_per_lane)
            if kp < self._pages_per_lane:
                self._kept_pages = kp
        # per-engine mesh-fallback record: filled (and warning-deduped) by
        # the attention dispatch while this engine's steps trace, so each
        # engine owns its fallback report regardless of other engines in
        # the process (see attention.use_decode_mesh's fallback_sink)
        self._mesh_fallback: set = set()
        self._state_sh = None
        admit_sh = step_sh = None
        if self.mesh is not None:
            admit_sh, step_sh = self._install_mesh()

        # chunked-prefill interleaving: admissions longer than the token
        # budget run as page-aligned chunks between decode steps (the
        # PREFILLING lane state). The dispatch plan is the single gate —
        # it folds in every policy/family predicate (see core.dispatch)
        self._chunked = (serving.prefill_budget_tokens is not None
                         and self._plan.chunked_prefill
                         and self._supports_ragged)
        # non-final chunks must keep the cursor aligned to the prompt
        # bucket (ragged prefill batches) *and* the page size (paged tail
        # writes address whole pages); the budget is validated to be a
        # multiple of both
        self._chunk_align = self.scfg.prompt_bucket
        if self._paged:
            self._chunk_align = math.lcm(self._chunk_align,
                                         self.cache_spec.page_size)
        # block-sparse kernel prefill: fresh-prompt chunks must reproduce
        # the kernel's per-tile dim-block selection, so cursors also stay
        # q_blk-aligned and the chunk step selects per tile
        # (attention._chunk_tile_mask). Prefix-shared admissions keep the
        # per-query selection their monolithic twin (_admit_prefix) uses.
        self._tile_q_blk = None
        if (self._chunked and self._plan.backend == "aqua-block-sparse"
                and cfg.aqua is not None and cfg.aqua.enabled
                and cfg.aqua.block_dims > 1
                and (cfg.aqua.kept_dims(cfg.attention.head_dim)
                     % cfg.aqua.block_dims == 0)
                and (self.mesh is None or self._plan.mesh_native)):
            self._tile_q_blk = cfg.aqua.prefill_q_blk
            self._chunk_align = math.lcm(self._chunk_align,
                                         self._tile_q_blk)

        # `use_top_k` is static: traffic without top-k compiles the decode
        # step without the per-row dynamic-threshold full-vocab sort
        self._admit = jax.jit(self._admit_impl,
                              static_argnames=("use_top_k",),
                              out_shardings=admit_sh)
        self._admit_paged = jax.jit(self._admit_paged_impl,
                                    static_argnames=("use_top_k",),
                                    out_shardings=admit_sh)
        self._admit_prefix = jax.jit(self._admit_prefix_impl,
                                     static_argnames=("use_top_k",),
                                     out_shardings=admit_sh)
        self._step = jax.jit(self._step_impl, static_argnames=("use_top_k",),
                             out_shardings=step_sh)
        # chunk steps: non-final chunks only advance the lane's cache (no
        # token sampled, lane bookkeeping untouched); the final chunk
        # fuses the admission tail (first-token sampling) exactly like the
        # monolithic admits. The paged first chunk also installs the
        # allocator's page-table row (later chunks inherit it from state)
        self._chunk = jax.jit(self._chunk_impl,
                              static_argnames=("select_q_blk",),
                              out_shardings=self._state_sh)
        self._chunk_paged = jax.jit(self._chunk_paged_impl,
                                    static_argnames=("select_q_blk",),
                                    out_shardings=self._state_sh)
        self._chunk_final = jax.jit(self._chunk_final_impl,
                                    static_argnames=("use_top_k",
                                                     "select_q_blk"),
                                    out_shardings=admit_sh)

    def _install_mesh(self):
        """Shard params/projections, derive decode-state + lane-state
        shardings, and install them on the model (sharding-preserving lane
        surgery) and the attention path (shard_map cores / shard_mapped
        Pallas kernels). Returns (admit, step) ``out_shardings`` pinning
        the jitted entry points."""
        from repro.distributed import sharding as dsh

        mesh, s = self.mesh, self.scfg
        self.params = jax.device_put(
            self.params, dsh.make_param_shardings(self.params, mesh))
        if self.proj is not None:
            self.proj = jax.device_put(self.proj, dsh.replicated(mesh))
        att = self.cfg.attention
        kvh = att.num_kv_heads if att is not None else 0
        # kernel-native layout: when the dispatch plan picked the
        # shard_mapped Pallas kernel path (contiguous or paged), the cache
        # keeps its slot axis (and dim-blocks, and pages) whole per shard
        # — unshardable axes replicate instead of absorbing into the
        # sequence stripe. The plan is the single source; _install_mesh no
        # longer recomputes the predicate (see repro.core.dispatch).
        self._kernel_native = self._plan.mesh_native
        state_struct = jax.eval_shape(
            lambda: self.model.init_decode_state(s.max_lanes, s.max_seq))
        self._state_sh = dsh.make_state_shardings(
            state_struct, mesh, kv_heads=kvh, batch=s.max_lanes,
            kernel_native=self._kernel_native)
        self.model.set_state_shardings(self._state_sh)
        self._lane_sh = dsh.make_lane_shardings(
            jax.eval_shape(lambda: _init_lane_state(s.max_lanes)), mesh)
        self._init_state = jax.jit(
            lambda: self.model.init_decode_state(s.max_lanes, s.max_seq),
            out_shardings=self._state_sh)
        self._init_lanes = jax.jit(lambda: _init_lane_state(s.max_lanes),
                                   out_shardings=self._lane_sh)
        # admissions interleave lanes across data shards so concurrent
        # prefill grafts and active-lane occupancy spread over the
        # data-parallel groups instead of piling onto shard 0's lane block
        dsize = math.prod(mesh.shape[a] for a in ("pod", "data")
                          if a in mesh.shape)
        if dsize > 1 and s.max_lanes % dsize == 0:
            per = s.max_lanes // dsize
            self._lane_order = [g * per + i for i in range(per)
                                for g in range(dsize)]
        vec = jax.sharding.NamedSharding(mesh,
                                         dsh.lane_pspec(mesh, s.max_lanes))
        rep = dsh.replicated(mesh)
        admit_sh = (rep, rep, self._state_sh, self._lane_sh)
        step_sh = (self._state_sh, self._lane_sh, vec, vec, vec)
        return admit_sh, step_sh

    def _use_mesh(self):
        """Trace-time context: installs (or clears) the decode mesh — and
        this engine's fallback sink — plus the hierarchical token-sparsity
        participation for the attention cores while this engine's steps
        trace. Both ride ContextVars and bake into the compiled
        executables, so concurrent engines stay independent."""
        from repro.core.attention import use_decode_mesh, use_token_sparsity
        stack = contextlib.ExitStack()
        stack.enter_context(use_decode_mesh(
            self.mesh, fallback_sink=self._mesh_fallback))
        stack.enter_context(use_token_sparsity(
            self._kept_pages, self.sparsity_spec.pin_recent_pages))
        return stack

    def mesh_fallback_events(self):
        """(backend, mode, reason) mesh-kernel fallbacks traced by THIS
        engine — empty means every Pallas-backend step really served
        shard_mapped (``launch.serve --verify`` asserts this). The reason
        strings are the ``repro.core.dispatch.REASON_*`` constants, so
        trace-time events line up with ``dispatch_plan().reasons`` — a
        plan with ``mesh_native=True`` predicts this stays empty."""
        return tuple(sorted(self._mesh_fallback))

    def dispatch_plan(self) -> DispatchPlan:
        """The engine's resolved :class:`repro.core.dispatch.DispatchPlan`
        — the one public inspection point for the serving dispatch:
        backend, cache layout (contiguous/paged), ``mesh_native`` (the
        contract ``launch.serve --expect-kernel-mesh`` gates on),
        prefix-sharing, and structured fallback ``reasons``."""
        return self._plan

    @property
    def paged(self) -> bool:
        """True when this engine serves from a block-paged KV pool."""
        return self._paged

    @property
    def kept_pages(self):
        """Per-lane participating-page count when hierarchical token
        sparsity engaged (``dispatch_plan().token_sparsity ==
        'hierarchical'`` and the resolved keep is a strict subset), else
        None — every page participates."""
        return self._kept_pages

    @property
    def pool_geometry(self):
        """(num_pages, pages_per_lane, page_size) in paged mode, None
        otherwise. ``num_pages < max_lanes * pages_per_lane`` means the
        pool is smaller than the lane-stripe layout it replaces."""
        if not self._paged:
            return None
        return (self._num_pages, self._pages_per_lane, self.cache_spec.page_size)

    # -- jitted bodies -------------------------------------------------
    def _finish_admit(self, logits, lanes: LaneState, lane, rng, max_new,
                      temperature, top_k, eos_id, uid, use_top_k):
        """Shared admission tail: sample the first token from the prefill
        logits and install the lane's bookkeeping."""
        keys = _request_keys(rng, jnp.full((1,), uid, jnp.int32),
                             jnp.zeros((1,), jnp.int32))
        tok = sample_tokens(logits, keys,
                            jnp.full((1,), temperature, jnp.float32),
                            jnp.full((1,), top_k, jnp.int32),
                            use_top_k=use_top_k)
        done = ((tok == eos_id) & (eos_id >= 0)) | (max_new <= 1)
        lanes = LaneState(
            last_token=lanes.last_token.at[lane].set(tok[0]),
            active=lanes.active.at[lane].set(~done[0]),
            generated=lanes.generated.at[lane].set(1),
            max_new=lanes.max_new.at[lane].set(max_new),
            temperature=lanes.temperature.at[lane].set(temperature),
            top_k=lanes.top_k.at[lane].set(top_k),
            eos_id=lanes.eos_id.at[lane].set(eos_id),
            uid=lanes.uid.at[lane].set(uid))
        return tok, done, lanes

    def _admit_impl(self, params, batch, state, lanes: LaneState, lane,
                    proj, rng, max_new, temperature, top_k, eos_id, uid,
                    use_top_k=True):
        """Prefill one request into ``lane`` and sample its first token.
        Returns (token (1,), done (1,), state, lanes)."""
        logits, state = self.model.prefill_into(params, batch,
                                                self.scfg.max_seq, state,
                                                lane, aqua_proj=proj)
        tok, done, lanes = self._finish_admit(logits, lanes, lane, rng,
                                              max_new, temperature, top_k,
                                              eos_id, uid, use_top_k)
        return tok, done, state, lanes

    def _set_table_row(self, state, lane, table_row):
        """Install the allocator's page-table row for ``lane`` (identical
        across the stacked layer axis)."""
        layers = dataclasses.replace(
            state.layers,
            page_table=state.layers.page_table.at[:, lane].set(table_row))
        return self.model.constrain_state(
            DecodeState(layers=layers, extra=state.extra))

    def _admit_paged_impl(self, params, batch, state, lanes: LaneState,
                          lane, table_row, proj, rng, max_new, temperature,
                          top_k, eos_id, uid, use_top_k=True):
        """Paged admission: prefill to a B=1 contiguous cache, then graft
        its slots into the pages the allocator mapped for ``lane``."""
        state = self._set_table_row(state, lane, table_row)
        logits, req_state = self.model.prefill(params, batch,
                                               self.scfg.max_seq,
                                               aqua_proj=proj)
        num_slots = (batch["tokens"].shape[1] if self._supports_ragged
                     else self._num_slots)
        state = self.model.graft_paged(state, req_state, lane, num_slots)
        tok, done, lanes = self._finish_admit(logits, lanes, lane, rng,
                                              max_new, temperature, top_k,
                                              eos_id, uid, use_top_k)
        return tok, done, state, lanes

    def _admit_prefix_impl(self, params, batch, state, lanes: LaneState,
                           lane, table_row, prefix_len, proj, rng, max_new,
                           temperature, top_k, eos_id, uid, use_top_k=True):
        """Prefix-shared paged admission: the prompt's page-aligned prefix
        is already mapped into ``lane`` (read-only, refcounted); only the
        tail prefills — zero recompute on the shared prefix."""
        state = self._set_table_row(state, lane, table_row)
        logits, state = self.model.prefill_with_prefix(
            params, batch, state, lane, prefix_len, aqua_proj=proj)
        tok, done, lanes = self._finish_admit(logits, lanes, lane, rng,
                                              max_new, temperature, top_k,
                                              eos_id, uid, use_top_k)
        return tok, done, state, lanes

    def _chunk_impl(self, params, batch, state, lane, cursor, proj,
                    select_q_blk=None):
        """Advance one PREFILLING lane by a non-final prefill chunk: the
        chunk's K/V lands in logical slots starting at ``cursor``; no
        token is sampled and lane bookkeeping is untouched (the lane
        emits nothing until the final chunk)."""
        _, state = self.model.prefill_chunk(params, batch, state, lane,
                                            cursor, aqua_proj=proj,
                                            select_q_blk=select_q_blk)
        return state

    def _chunk_paged_impl(self, params, batch, state, lane, table_row,
                          cursor, proj, select_q_blk=None):
        """First paged chunk: install the allocator's page-table row,
        then advance the lane (subsequent chunks read the row from
        state)."""
        state = self._set_table_row(state, lane, table_row)
        _, state = self.model.prefill_chunk(params, batch, state, lane,
                                            cursor, aqua_proj=proj,
                                            select_q_blk=select_q_blk)
        return state

    def _chunk_final_impl(self, params, batch, state, lanes: LaneState,
                          lane, cursor, proj, rng, max_new, temperature,
                          top_k, eos_id, uid, use_top_k=True,
                          select_q_blk=None):
        """Final prefill chunk: advance the cache to the full prompt and
        sample the request's first token — the chunked twin of the
        monolithic admission tail."""
        logits, state = self.model.prefill_chunk(params, batch, state,
                                                 lane, cursor,
                                                 aqua_proj=proj,
                                                 select_q_blk=select_q_blk)
        tok, done, lanes = self._finish_admit(logits, lanes, lane, rng,
                                              max_new, temperature, top_k,
                                              eos_id, uid, use_top_k)
        return tok, done, state, lanes

    def _step_impl(self, params, state, lanes: LaneState, proj, rng,
                   use_top_k=True):
        """One decode step over all lanes: model step + per-lane sampling
        + stop detection, all compiled. Inactive lanes are frozen via
        ``write_mask`` and report ``pad_id``."""
        logits, state = self.model.decode_step(params, state,
                                               lanes.last_token,
                                               aqua_proj=proj,
                                               write_mask=lanes.active)
        keys = _request_keys(rng, lanes.uid, lanes.generated)
        tok = sample_tokens(logits, keys, lanes.temperature, lanes.top_k,
                            use_top_k=use_top_k)
        tok = jnp.where(lanes.active, tok, self.scfg.pad_id)
        emitted = lanes.active
        generated = lanes.generated + emitted.astype(jnp.int32)
        done = emitted & (((tok == lanes.eos_id) & (lanes.eos_id >= 0))
                          | (generated >= lanes.max_new))
        lanes = dataclasses.replace(
            lanes, last_token=jnp.where(emitted, tok, lanes.last_token),
            active=lanes.active & ~done, generated=generated)
        return state, lanes, tok, emitted, done

    # -- host-side drive ----------------------------------------------
    def _normalize(self, req: Request) -> Request:
        s = self.scfg
        out = dataclasses.replace(
            req,
            max_new_tokens=(s.max_new_tokens if req.max_new_tokens is None
                            else req.max_new_tokens),
            temperature=(s.temperature if req.temperature is None
                         else req.temperature),
            top_k=s.top_k if req.top_k is None else req.top_k,
            eos_id=s.eos_id if req.eos_id is None else req.eos_id)
        if out.prompt_len < 1:
            raise ValueError(f"request {req.uid}: empty prompt")
        if out.prompt_len + out.max_new_tokens > s.max_seq:
            raise ValueError(
                f"request {req.uid}: prompt_len={out.prompt_len} + "
                f"max_new_tokens={out.max_new_tokens} exceeds "
                f"max_seq={s.max_seq}")
        return out

    def _prefill_batch(self, req: Request,
                       budget: Optional[int] = None) -> Dict[str, jax.Array]:
        toks = np.asarray(req.tokens, np.int32).reshape(1, -1)
        s = toks.shape[1]
        if budget is None:
            budget = self.scfg.max_seq
        if self._supports_ragged:
            bucket = self.scfg.prompt_bucket
            padded_len = max(bucket, ((s + bucket - 1) // bucket) * bucket)
            # never pad past the cache: a padded prefill longer than
            # the remaining slot budget would roll the prompt prefix out
            # of the cache (or, prefix-shared, out of the reserved pages)
            padded_len = min(padded_len, budget)
            padded = np.zeros((1, padded_len), np.int32)
            padded[0, :s] = toks[0]
            batch = {"tokens": jnp.asarray(padded),
                     "lengths": jnp.asarray([s], jnp.int32)}
        else:
            batch = {"tokens": jnp.asarray(toks)}
        if req.extra_inputs:
            batch.update(req.extra_inputs)
        return batch

    # -- paged admission planning (host side) --------------------------
    def _padded_prompt_len(self, prompt_len: int, budget: int) -> int:
        """Prefill length after bucket padding (mirrors _prefill_batch)."""
        if not self._supports_ragged:
            return prompt_len
        bucket = self.scfg.prompt_bucket
        padded = max(bucket, ((prompt_len + bucket - 1) // bucket) * bucket)
        return min(padded, budget)

    def _plan_pages(self, req: Request):
        """Decide the page reservation for an admission: how many pages
        the request needs for its whole lifetime (prefill + decode — the
        jitted steps never allocate), and which of them are shared prefix
        pages already in the pool. Returns (shared_pages, num_new) or None
        when the pool can't cover it yet (the request waits)."""
        ps = self.cache_spec.page_size
        shared: list = []
        if self._supports_ragged:
            if self._prefix_ok and not req.extra_inputs:
                # only full prompt pages are shareable, and at least one
                # tail token must remain to produce the prefill logits
                shared = self.page_pool.lookup_prefix(
                    req.tokens)[:(req.prompt_len - 1) // ps]
            prefix_len = len(shared) * ps
            tail_padded = self._padded_prompt_len(
                req.prompt_len - prefix_len, self.scfg.max_seq - prefix_len)
            total_slots = min(max(prefix_len + tail_padded,
                                  req.prompt_len + req.max_new_tokens),
                              self._num_slots)
            total_pages = -(-total_slots // ps)
        else:
            # window/H2O policies place slots across the whole logical
            # stripe (ring wrap, eviction) — reserve every page
            total_pages = self._pages_per_lane
        num_new = total_pages - len(shared)
        if not self.page_pool.can_reserve(num_new):
            return None
        return shared, num_new

    def _pop_admission(self, sched: LaneScheduler, now: float):
        """Pop the next request to admit, with its page plan: (req, plan),
        or (None, None) when the pool covers no arrived request yet. In
        paged mode a request only admits while the page pool covers its
        whole lifetime (workload-to-memory scheduling, not OOM); when the
        queue head can't fit, up to ``admission_lookahead`` later arrivals
        may admit first (bounded first-fit, no head-of-line blocking) and
        the head keeps its exact queue position for the next pass."""
        skip = 0
        unbounded = sched.num_active == 0   # nothing will retire
        while True:
            cand = sched.pop_admissible(now, skip=skip)
            if cand is None:
                break
            if not self._paged:
                return cand, None
            plan = self._plan_pages(cand)
            if plan is not None:
                return cand, plan
            sched.unpop(cand)
            skip += 1
            if not unbounded and skip >= self.scfg.admission_lookahead:
                break
        if skip > 0 and sched.num_active == 0:
            raise RuntimeError(
                f"page pool ({self._num_pages} pages of "
                f"{self.cache_spec.page_size}) cannot fit any of the "
                f"{skip} arrived request(s) even with every lane free — "
                "raise CacheSpec.num_pages")
        return None, None

    def _admit_len(self, req: Request, page_plan) -> int:
        """Tokens an admission prefills after bucket padding (a shared
        prefix excluded; mirrors ``_dispatch_admit``'s batches)."""
        prefix_len = 0
        if self._paged and page_plan is not None:
            prefix_len = len(page_plan[0]) * self.cache_spec.page_size
        return self._padded_prompt_len(req.prompt_len - prefix_len,
                                       self.scfg.max_seq - prefix_len)

    # -- chunked-prefill planning (host side) --------------------------
    def _should_chunk(self, req: Request, page_plan) -> bool:
        """Chunk this admission? Only when the engine interleaves, the
        request is token-only, and the prefill actually exceeds the
        budget — short prompts keep the monolithic admit (exact same
        path as a non-chunked engine, kernel-capable under a mesh)."""
        if not self._chunked or req.extra_inputs:
            return False
        return (self._admit_len(req, page_plan)
                > self.scfg.prefill_budget_tokens)

    def _admit_chunked(self, sched: LaneScheduler, req: Request,
                       page_plan) -> tuple:
        """Admit a long prompt into a PREFILLING lane: reserve its pages
        for the whole lifetime (paged) and set the chunk cursor. No
        device work happens here — the serve loop spends the budget
        chunk by chunk. Returns (lane, job) host bookkeeping."""
        lane = sched.assign(req, prefilling=True)
        job = {"req": req, "row": None, "row_set": False,
               "register": False, "pages": None,
               "select": self._tile_q_blk}
        if self._paged:
            shared, num_new = page_plan
            pool = self.page_pool
            pages = pool.reserve(lane, shared, num_new)
            assert pages is not None      # _plan_pages checked can_reserve
            row = np.full((self._pages_per_lane,), -1, np.int32)
            row[:len(pages)] = pages
            job["row"] = jnp.asarray(row)
            job["pages"] = pages
            # prefix registration is deferred until the final chunk has
            # written the whole prompt: sharers read shared pages at
            # admission, so a half-written prompt must stay unindexed
            job["register"] = self._prefix_ok and not req.extra_inputs
            if shared:
                prefix_len = len(shared) * self.cache_spec.page_size
                pool.prefix_hits += 1
                pool.tokens_saved += prefix_len
                sched.begin_prefill(lane, prefix_len, req.prompt_len)
                # prefix-shared chunks match _admit_prefix's per-query
                # selection (the shared-prefix cursor is page-, not
                # necessarily q_blk-aligned)
                job["select"] = None
        return lane, job

    def _chunk_padded_len(self, cursor: int, count: int) -> int:
        """Tokens a chunk's prefill batch holds after bucket padding —
        the chunk's budget cost (mirrors ``_prefill_batch``'s padding,
        clamped so the padded tail never writes past the cache)."""
        bucket = self.scfg.prompt_bucket
        padded = max(bucket, ((count + bucket - 1) // bucket) * bucket)
        cap = self._num_slots if self._paged else self.scfg.max_seq
        return min(padded, cap - cursor)

    def _chunk_batch(self, req: Request, cursor: int,
                     count: int) -> Dict[str, jax.Array]:
        """Prefill batch for prompt tokens [cursor, cursor + count):
        bucket-padded with ragged ``lengths``. Non-final chunks are
        align-sized (multiples of lcm(prompt_bucket, page_size)) so their
        padding is empty and the next cursor stays page-aligned; only the
        final chunk is ragged."""
        toks = np.asarray(req.tokens, np.int32)
        padded_len = self._chunk_padded_len(cursor, count)
        padded = np.zeros((1, padded_len), np.int32)
        padded[0, :count] = toks[cursor:cursor + count]
        return {"tokens": jnp.asarray(padded),
                "lengths": jnp.asarray([count], jnp.int32)}

    def _dispatch_admit(self, req: Request, lane: int, state, lanes, rng,
                        use_top_k: bool, page_plan=None):
        """Run the right jitted admission step for ``req`` (contiguous,
        paged, or paged prefix-shared). ``page_plan`` is the
        (shared_pages, num_new) reservation decided by :meth:`_plan_pages`
        for this request (required in paged mode)."""
        common = dict(use_top_k=use_top_k)
        if not self._paged:
            with self._use_mesh():
                return self._admit(
                    self.params, self._prefill_batch(req), state, lanes,
                    jnp.int32(lane), self.proj, rng, req.max_new_tokens,
                    req.temperature, req.top_k, req.eos_id, req.uid,
                    **common)
        pool = self.page_pool
        shared, num_new = page_plan
        pages = pool.reserve(lane, shared, num_new)
        assert pages is not None      # _plan_pages checked can_reserve
        row = np.full((self._pages_per_lane,), -1, np.int32)
        row[:len(pages)] = pages
        row = jnp.asarray(row)
        ps = self.cache_spec.page_size
        if shared:
            prefix_len = len(shared) * ps
            pool.prefix_hits += 1
            pool.tokens_saved += prefix_len
            tail = dataclasses.replace(
                req, tokens=np.asarray(req.tokens)[prefix_len:])
            batch = self._prefill_batch(tail, budget=self.scfg.max_seq
                                        - prefix_len)
            with self._use_mesh():
                out = self._admit_prefix(
                    self.params, batch, state, lanes, jnp.int32(lane), row,
                    jnp.int32(prefix_len), self.proj, rng,
                    req.max_new_tokens, req.temperature, req.top_k,
                    req.eos_id, req.uid, **common)
        else:
            batch = self._prefill_batch(req)
            with self._use_mesh():
                out = self._admit_paged(
                    self.params, batch, state, lanes, jnp.int32(lane), row,
                    self.proj, rng, req.max_new_tokens, req.temperature,
                    req.top_k, req.eos_id, req.uid, **common)
        if self._prefix_ok and not req.extra_inputs:
            # both branches register: a prompt that *extends* a shared
            # prefix by further full pages indexes those pages too, so
            # later duplicates share the whole prompt, not just the part
            # the first registrant happened to cover
            pool.register_prefix(req.tokens, pages, req.prompt_len)
        return out

    def _retire(self, sched: LaneScheduler, lane: int) -> None:
        sched.retire(lane)
        if self._paged:
            self.page_pool.release(lane)

    def serve(self, requests: Iterable[Request]) -> Iterator[StreamEvent]:
        """Drive a trace of requests to completion, yielding one
        ``StreamEvent`` per generated token (in emission order). Aggregate
        trace statistics land in ``self.stats``; pool statistics (paged
        mode) in ``self.page_pool``.

        Chunked-prefill interleaving (``prefill_budget_tokens`` set and
        the dispatch plan admits it): prompts whose padded prefill
        exceeds the budget are admitted immediately into PREFILLING lanes
        and advance by at most the budget between decode steps, so a
        decoding lane never stalls behind a monolithic prefill longer
        than one chunk. Tokens are greedy-identical to monolithic
        admission — sampling keys fold the request uid and token counter,
        and chunk boundaries never change what a token computes."""
        sched = LaneScheduler(self.scfg.max_lanes,
                              lane_order=self._lane_order)
        use_top_k = False
        for r in requests:
            r = self._normalize(r)
            use_top_k |= r.top_k > 0
            sched.submit(r)
        if self._paged:
            self.page_pool = PagePool(self._num_pages, self.cache_spec.page_size,
                                      prefix_sharing=self._prefix_ok)

        rng = jax.random.fold_in(self._base_rng, self._serves)
        self._serves += 1
        if self.mesh is not None:
            state, lanes = self._init_state(), self._init_lanes()
        else:
            state = self.model.init_decode_state(self.scfg.max_lanes,
                                                 self.scfg.max_seq)
            lanes = _init_lane_state(self.scfg.max_lanes)
        # exposed for inspection/tests (terminal lane state after a drive)
        self.last_state, self.last_lanes = state, lanes
        stats = ScheduleStats()
        self.stats = stats
        emitted_count: Dict[int, int] = {}
        last_emit: Dict[int, float] = {}   # uid -> perf_counter of last yield
        jobs: Dict[int, dict] = {}         # PREFILLING lanes' bookkeeping
        budget = self.scfg.prefill_budget_tokens
        now = 0.0

        def finish_reason(tok: int, req: Request) -> str:
            return "eos" if (req.eos_id is not None and req.eos_id >= 0
                             and tok == req.eos_id) else "length"

        def record_emit(uid: int) -> None:
            t = time.perf_counter()
            if uid in last_emit:
                stats.itl_gaps.append(t - last_emit[uid])
            last_emit[uid] = t

        def first_token(req: Request, lane: int, tok, done) -> StreamEvent:
            t, d = int(tok[0]), bool(done[0])
            stats.tokens_emitted += 1
            emitted_count[req.uid] = 1
            record_emit(req.uid)
            if d:
                self._retire(sched, lane)
                stats.requests_finished += 1
                last_emit.pop(req.uid, None)
            return StreamEvent(req.uid, t, 0, d,
                               finish_reason(t, req) if d else "")

        caller_ns = 0      # suspended at yield since the last engine.step

        def hand_over(ev: StreamEvent):
            nonlocal caller_ns
            t = time.perf_counter_ns()
            yield ev
            caller_ns += time.perf_counter_ns() - t

        while sched.has_work:
            # admissions: fill free lanes with every arrived request the
            # page pool covers (``_pop_admission``)
            while sched.can_admit(now):
                with telemetry.span("engine.admit") as sp:
                    req, page_plan = self._pop_admission(sched, now)
                    if req is None:
                        break
                    sp.set(uid=req.uid, prompt=req.prompt_len)
                    if self._should_chunk(req, page_plan):
                        lane, job = self._admit_chunked(sched, req,
                                                        page_plan)
                        jobs[lane] = job
                        stats.chunked_admissions += 1
                        continue
                    sp.set(padded=self._admit_len(req, page_plan))
                    lane = sched.assign(req)
                    tok, done, state, lanes = self._dispatch_admit(
                        req, lane, state, lanes, rng, use_top_k,
                        page_plan=page_plan)
                    self.last_state, self.last_lanes = state, lanes
                    ev = first_token(req, lane, tok, done)
                yield from hand_over(ev)
            if sched.num_active == 0:
                if sched.has_pending:
                    now = max(now, sched.next_arrival)   # idle-jump
                    continue
                break

            # spend the prefill budget on PREFILLING lanes, oldest first
            # (strict FIFO: when the oldest lane's next chunk doesn't fit
            # the remaining budget, younger lanes wait too — no
            # starvation). The final chunk fuses first-token sampling and
            # flips the lane to DECODING.
            if self._chunked and sched.num_prefilling > 0:
                left = budget
                for lane in sched.prefilling_lanes():
                    job = jobs[lane]
                    req = job["req"]
                    cursor = sched.prefill_cursor(lane)
                    rem = sched.prefill_remaining(lane)
                    if rem > left:
                        # non-final chunk, align-sized so the next cursor
                        # stays bucket- and page-aligned
                        n = (left // self._chunk_align) * self._chunk_align
                        if n <= 0:
                            break
                        with telemetry.span("engine.prefill_chunk",
                                            uid=req.uid, tokens=n):
                            batch = self._chunk_batch(req, cursor, n)
                            with self._use_mesh():
                                if (job["row"] is not None
                                        and not job["row_set"]):
                                    state = self._chunk_paged(
                                        self.params, batch, state,
                                        jnp.int32(lane), job["row"],
                                        jnp.int32(cursor), self.proj,
                                        select_q_blk=job["select"])
                                    job["row_set"] = True
                                else:
                                    state = self._chunk(
                                        self.params, batch, state,
                                        jnp.int32(lane), jnp.int32(cursor),
                                        self.proj,
                                        select_q_blk=job["select"])
                        self.last_state = state
                        sched.advance_prefill(lane, n)
                        stats.prefill_chunks += 1
                        left -= n
                        if left <= 0:
                            break
                        continue
                    padded = self._chunk_padded_len(cursor, rem)
                    if padded > left:
                        break
                    jobs.pop(lane)
                    with telemetry.span("engine.prefill_chunk",
                                        uid=req.uid, tokens=rem):
                        batch = self._chunk_batch(req, cursor, rem)
                        with self._use_mesh():
                            tok, done, state, lanes = self._chunk_final(
                                self.params, batch, state, lanes,
                                jnp.int32(lane), jnp.int32(cursor),
                                self.proj, rng, req.max_new_tokens,
                                req.temperature, req.top_k, req.eos_id,
                                req.uid, use_top_k=use_top_k,
                                select_q_blk=job["select"])
                        self.last_state, self.last_lanes = state, lanes
                        sched.advance_prefill(lane, rem)
                        sched.mark_decoding(lane)
                        stats.prefill_chunks += 1
                        left -= padded
                        if job["register"]:
                            self.page_pool.register_prefix(
                                req.tokens, job["pages"], req.prompt_len)
                        ev = first_token(req, lane, tok, done)
                    yield from hand_over(ev)
                    if left <= 0:
                        break

            # decode step over the DECODING lanes (PREFILLING lanes ride
            # along frozen under the write_mask). Skipped while only
            # prefills are in flight — time still advances, so arrivals
            # keep flowing while a long prompt chunks in.
            if sched.num_decoding > 0:
                with telemetry.span("engine.step", step=stats.decode_steps,
                                    caller_ms=caller_ns / 1e6) as sp:
                    with self._use_mesh():
                        state, lanes, tok, emitted, done = self._step(
                            self.params, state, lanes, self.proj, rng,
                            use_top_k=use_top_k)
                    self.last_state, self.last_lanes = state, lanes
                    with telemetry.span("engine.step.wait"):
                        tok_h = np.asarray(tok)
                        em_h = np.asarray(emitted)
                        done_h = np.asarray(done)
                    emitting = int(em_h.sum())
                    sp.set(lanes=emitting)
                caller_ns = 0
                stats.decode_steps += 1
                stats.occupancy_sum += emitting
                if self._paged:
                    self.page_pool.sample_utilization()
                now += 1.0
                for lane in sched.decoding_lanes():
                    if not em_h[lane]:
                        continue
                    req = sched.request_in(lane)
                    t, d = int(tok_h[lane]), bool(done_h[lane])
                    idx = emitted_count[req.uid]
                    emitted_count[req.uid] = idx + 1
                    stats.tokens_emitted += 1
                    record_emit(req.uid)
                    if d:
                        self._retire(sched, lane)
                        stats.requests_finished += 1
                        last_emit.pop(req.uid, None)
                    yield from hand_over(StreamEvent(
                        req.uid, t, idx, d,
                        finish_reason(t, req) if d else ""))
            else:
                now += 1.0

    def run(self, requests: Iterable[Request]
            ) -> Dict[int, RequestOutput]:
        """Serve to completion and collect per-request terminal outputs."""
        reqs = {r.uid: r for r in requests}
        outs = {uid: RequestOutput(uid=uid, prompt_len=r.prompt_len)
                for uid, r in reqs.items()}
        for ev in self.serve(reqs.values()):
            o = outs[ev.uid]
            if ev.index == 0:
                o.admitted_at = self.stats.decode_steps
            o.tokens.append(ev.token)
            if ev.finished:
                o.finish_reason = ev.finish_reason
                o.finished_at = self.stats.decode_steps
        return outs

    def cache_bytes(self) -> int:
        """Lane-state KV footprint (shape-only, no device allocation).
        Pool-based when paging is on: the page pool is counted once, not
        ``lanes × max_seq`` — the HBM-ratio win the serving bench reports.
        See :func:`decode_state_bytes`."""
        return decode_state_bytes(self.model, self.scfg.max_lanes,
                                  self.scfg.max_seq)
