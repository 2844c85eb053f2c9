"""Model protocol and decode-state container."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


@jax.tree_util.register_dataclass
@dataclass
class DecodeState:
    """Serving state: one cache pytree per layer plus model-level extras
    (e.g. whisper's precomputed cross-attention K/V)."""

    layers: Tuple[Any, ...]
    extra: Dict[str, Any] = field(default_factory=dict)


@dataclass(frozen=True)
class PagingSpec:
    """Block-paged KV cache geometry installed on a model by the serving
    engine (``LM.enable_paging``): ``init_decode_state`` then allocates a
    global page pool + per-lane page tables instead of contiguous per-lane
    slot stripes (repro.core.kvcache.PagedAttnCache).

    ``kv_dtype``/``scale_granularity``/``hot_pages`` carry the engine's
    resolved ``configs.base.QuantSpec``: ``"int8"`` pools store per-page
    symmetric-quantized K̂/V with f32 scales beside the page table, and
    ``hot_pages > 0`` adds a write-through full-precision overlay for
    that many hot-resident pages (mixed precision)."""

    page_size: int
    num_pages: int
    kv_dtype: str = "bf16"                # bf16 | int8
    scale_granularity: str = "page_head"  # page_head | page
    hot_pages: int = 0


class LM:
    """Base class: subclasses implement the per-family wiring.

    All methods are pure functions of (params, inputs) and jit-compatible;
    ``self`` only carries the static config.
    """

    def __init__(self, cfg: ModelConfig):
        cfg.validate()
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.dtype)
        self.param_dtype = jnp.dtype(cfg.param_dtype)
        # mesh-native serving: DecodeState-shaped pytree of NamedShardings
        # (None = single-device; see set_state_shardings)
        self._state_shardings = None
        # block-paged serving: PagingSpec or None (see enable_paging)
        self._paging: Optional[PagingSpec] = None

    # -- block-paged serving ------------------------------------------
    #: families that implement the paged decode-state layout
    supports_paging = False

    def enable_paging(self, spec: Optional[PagingSpec]) -> None:
        """Install (or clear) the paged cache geometry. While installed,
        ``init_decode_state`` returns the page-pool layout and the paged
        lane-surgery APIs (``graft_paged`` / ``prefill_with_prefix`` /
        ``reset_lane``) become the admission path."""
        if spec is not None and not self.supports_paging:
            raise NotImplementedError(
                f"family {self.cfg.family!r} does not support the paged "
                "KV cache (dense-transformer families only)")
        self._paging = spec

    @property
    def paging(self) -> Optional[PagingSpec]:
        return self._paging

    def graft_paged(self, state: DecodeState, req_state: DecodeState,
                    lane: jax.Array, num_slots: int) -> DecodeState:
        """Copy logical slots [0, num_slots) of a B=1 contiguous prefill
        cache into ``lane``'s pages of a paged multi-lane state."""
        raise NotImplementedError

    def prefill_with_prefix(self, params, batch, state: DecodeState,
                            lane: jax.Array, prefix_len: jax.Array,
                            aqua_proj: Optional[jax.Array] = None,
                            select_q_blk: Optional[int] = None
                            ) -> Tuple[jax.Array, DecodeState]:
        """Prefill only the *tail* of a request whose page-aligned prompt
        prefix is already mapped into ``lane`` (prefix sharing): tail
        queries attend to the shared prefix K/V read from the pool, and
        only the tail's K/V is written (into private pages)."""
        raise NotImplementedError

    def prefill_chunk(self, params, batch, state: DecodeState,
                      lane: jax.Array, prefix_len: jax.Array,
                      aqua_proj: Optional[jax.Array] = None,
                      select_q_blk: Optional[int] = None
                      ) -> Tuple[jax.Array, DecodeState]:
        """Advance ``lane``'s cache by one prefill chunk: the chunk's
        queries attend to everything the lane already holds in logical
        slots ``[0, prefix_len)`` (earlier chunks — or a shared prefix —
        of the same prompt) plus themselves, and only the chunk's K/V is
        written, starting at slot ``prefix_len``. Returns next-token
        logits for the chunk's last valid row (meaningful on the final
        chunk) and the updated state. ``select_q_blk`` (static) switches
        the AQUA dim-block selection to the block-sparse kernel's
        per-tile aggregation so chunked admissions reproduce the
        monolithic kernel's selection (cursors must be multiples of it).
        Families whose decode state is not a slot cache (recurrent
        state) cannot resume mid-prompt and keep monolithic admission
        (see ``core.dispatch``)."""
        raise NotImplementedError

    # -- mesh-native serving ------------------------------------------
    def set_state_shardings(self, shardings) -> None:
        """Install decode-state shardings (a DecodeState-shaped pytree of
        ``NamedSharding`` leaves, or None to clear). While installed, the
        lane-surgery APIs re-constrain their results, so a B=1 prefill
        graft into a sharded multi-lane state stays on the mesh — GSPMD
        sees an explicit anchor instead of inferring (and possibly
        resharding) through the scatter, and nothing round-trips the host.
        Constraints apply under jit; the serving engine only grafts inside
        its jitted admission step."""
        self._state_shardings = shardings

    def constrain_state(self, state: DecodeState) -> DecodeState:
        if self._state_shardings is None:
            return state
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s),
            state, self._state_shardings)

    # -- required API -------------------------------------------------
    def init(self, rng: jax.Array):
        raise NotImplementedError

    def forward(self, params, batch: Dict[str, jax.Array],
                aqua_proj: Optional[jax.Array] = None, capture: bool = False):
        """Full-sequence logits (B, S, V) [, aux]."""
        raise NotImplementedError

    def init_decode_state(self, batch_size: int, max_seq: int) -> DecodeState:
        raise NotImplementedError

    def prefill(self, params, batch, max_seq: int,
                aqua_proj: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, DecodeState]:
        raise NotImplementedError

    def decode_step(self, params, state: DecodeState, tokens: jax.Array,
                    aqua_proj: Optional[jax.Array] = None,
                    write_mask: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, DecodeState]:
        """tokens: (B,) int32 -> (logits (B, V), new state).

        ``write_mask`` (B,) bool, when supported by the family, freezes
        masked-off rows' cache state (inactive scheduler lanes ride the
        batched step without mutating their lane).
        """
        raise NotImplementedError

    # -- lane surgery (continuous-batching serving) -------------------
    #
    # A *lane* is one batch row of a DecodeState. Every stacked-layer
    # leaf in this framework carries layers at axis 0 and batch at axis 1
    # ((L, B, ...)); model-level extras carry batch at axis 1 as well
    # (e.g. whisper's cross K/V (L, B, S, KV, D)), so lane surgery is
    # uniform pytree indexing. Families that break this invariant must
    # override these methods.

    @jax.named_scope("kv.write")
    def insert_lane(self, state: DecodeState, req_state: DecodeState,
                    lane: jax.Array) -> DecodeState:
        """Graft a single-request (B=1) decode state into batch row
        ``lane`` of a multi-lane state. Overwrites the lane completely —
        K/V slots, positions, count, and H2O ``acc_score`` (and AQUA
        dim-sliced K lanes ride along: the leaves are already projected/
        sliced identically on both sides since shapes derive from the same
        config + max_seq). jit-safe with a traced ``lane``; when state
        shardings are installed the grafted state is re-constrained to
        them (sharding-preserving lane surgery)."""
        lane_set = lambda dst, src: dst.at[:, lane].set(src[:, 0])
        return self.constrain_state(DecodeState(
            layers=jax.tree.map(lane_set, state.layers, req_state.layers),
            extra=jax.tree.map(lane_set, state.extra, req_state.extra),
        ))

    def reset_lane(self, state: DecodeState, lane: jax.Array,
                   max_seq: int) -> DecodeState:
        """Return ``state`` with batch row ``lane`` restored to the
        freshly-initialized (empty-cache) condition."""
        return self.insert_lane(state, self.init_decode_state(1, max_seq),
                                lane)

    def prefill_into(self, params, batch, max_seq: int, state: DecodeState,
                     lane: jax.Array,
                     aqua_proj: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, DecodeState]:
        """Prefill one request (batch size 1, optionally ragged via
        ``batch["lengths"]``) and graft its cache into ``lane`` of an
        occupied multi-lane state. Returns (next-token logits (1, V),
        updated lanes state)."""
        logits, req_state = self.prefill(params, batch, max_seq, aqua_proj)
        return logits, self.insert_lane(state, req_state, lane)

    @staticmethod
    def freeze_rows(new_state: DecodeState, old_state: DecodeState,
                    write_mask: jax.Array, batch_axis: int = 1
                    ) -> DecodeState:
        """Keep ``old_state`` for rows where ``write_mask`` is False.

        State-level fallback for families whose decode step rewrites the
        whole (small) recurrent state anyway; attention caches use the
        targeted per-slot masking in ``kvcache.insert`` instead (a full
        cache-sized ``where`` would double decode HBM traffic)."""
        def merge(new, old):
            shape = [1] * new.ndim
            shape[batch_axis] = write_mask.shape[0]
            return jnp.where(write_mask.reshape(shape), new, old)
        return DecodeState(
            layers=jax.tree.map(merge, new_state.layers, old_state.layers),
            extra=jax.tree.map(merge, new_state.extra, old_state.extra))

    # -- provided -----------------------------------------------------
    def loss(self, params, batch: Dict[str, jax.Array]):
        from repro.models.layers import cross_entropy
        logits = self.forward(params, batch)
        if isinstance(logits, tuple):
            logits, aux = logits
        else:
            aux = {}
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        l = cross_entropy(logits, labels, mask)
        if "aux_loss" in aux:
            l = l + aux["aux_loss"]
        return l, {"ce": l}
