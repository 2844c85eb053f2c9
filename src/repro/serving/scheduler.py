"""Request scheduler for the continuous-batching engine.

Host-side bookkeeping only — all device work (prefill, lane surgery,
the jitted decode step) lives in ``repro.serving.engine``. The split
keeps the scheduler trivially testable and lets later PRs swap policies
(priority queues, prefill batching, preemption) without touching the
compiled step.

Request lifecycle::

    submit --> pending (arrival-ordered) --> admitted into a free *lane*
           --> [PREFILLING (chunked admission, no tokens emitted) -->]
               DECODING (one token per engine step) --> retired
               (EOS, length limit) --> lane freed for the next request

A *lane* is one batch row of the engine's shared decode state; the
number of lanes is fixed (``ServingConfig.max_lanes``) so the decode
step always runs at a static, jit-friendly shape regardless of how many
requests are in flight.

Chunked prefill (``ServingConfig.prefill_budget_tokens``) admits a long
prompt immediately into a ``LANE_PREFILLING`` lane: the engine advances
its per-lane *prefill cursor* by at most the token budget between decode
steps, and the lane transitions to ``LANE_DECODING`` (first token
sampled) only when the cursor reaches the prompt length. The scheduler
owns the cursor bookkeeping and the state machine; the engine owns the
device work and the budget spending loop.
"""

from __future__ import annotations

import bisect
import hashlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# Lane states (``LaneScheduler.lane_state``). A free lane has state None.
LANE_PREFILLING = "prefilling"
LANE_DECODING = "decoding"


@dataclass
class Request:
    """One generation request. ``None`` sampling fields inherit the
    engine's ``ServingConfig`` defaults at submission time.

    ``arrival`` is measured in decode-step time units — the engine admits
    a request once its arrival time is <= the current step counter, which
    makes traces (e.g. Poisson arrivals) exactly reproducible.
    """

    uid: int
    tokens: np.ndarray  # (S,) int32 prompt
    max_new_tokens: Optional[int] = None  # includes the prefill-sampled token
    temperature: Optional[float] = None
    top_k: Optional[int] = None
    eos_id: Optional[int] = None
    arrival: float = 0.0
    # modality frontend inputs spliced into the prefill batch
    # (e.g. {"frames": ...} for whisper, {"patches": ...} for VLMs)
    extra_inputs: Optional[dict] = None

    @property
    def prompt_len(self) -> int:
        return int(np.asarray(self.tokens).shape[-1])


@dataclass
class StreamEvent:
    """One streamed output token. ``index`` counts tokens within the
    request (0 = the token sampled from the prefill logits)."""

    uid: int
    token: int
    index: int
    finished: bool = False
    finish_reason: str = ""  # "eos" | "length" when finished


@dataclass
class RequestOutput:
    """Collected terminal result for one request (``engine.run``)."""

    uid: int
    prompt_len: int
    tokens: List[int] = field(default_factory=list)
    finish_reason: str = ""
    admitted_at: int = -1  # engine step counter at admission
    finished_at: int = -1


@dataclass
class ScheduleStats:
    """Aggregate trace statistics for one ``serve``/``run`` drive."""

    decode_steps: int = 0
    tokens_emitted: int = 0
    requests_finished: int = 0
    occupancy_sum: int = 0  # sum over steps of active lanes
    # chunked-prefill interleaving
    prefill_chunks: int = 0  # chunk steps executed between decode steps
    chunked_admissions: int = 0  # requests admitted in PREFILLING state
    # wall-clock gaps between consecutive emitted tokens of one request,
    # in seconds (every request's gaps pooled) — the tail of this
    # distribution is what chunked prefill exists to cut
    itl_gaps: List[float] = field(default_factory=list)

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / max(self.decode_steps, 1)

    @property
    def max_itl(self) -> float:
        return max(self.itl_gaps) if self.itl_gaps else 0.0

    def itl_percentile(self, pct: float) -> float:
        """Inter-token latency percentile in seconds (0 if no gaps)."""
        if not self.itl_gaps:
            return 0.0
        return float(np.percentile(np.asarray(self.itl_gaps), pct))

    def slo_miss_rate(self, threshold_s: float) -> float:
        """Fraction of inter-token gaps exceeding ``threshold_s``."""
        if not self.itl_gaps:
            return 0.0
        misses = sum(1 for g in self.itl_gaps if g > threshold_s)
        return misses / len(self.itl_gaps)


class LaneScheduler:
    """Admit/retire requests into a fixed set of decode lanes.

    Pending requests are kept arrival-ordered (FIFO among simultaneous
    arrivals by submission order); lanes are recycled LIFO so repeated
    light traffic stays in a warm lane prefix.

    ``lane_order`` overrides the default 0..L-1 assignment preference —
    the mesh-native engine passes an order interleaved across the data
    shards of its lane sharding, so light traffic spreads over the
    data-parallel groups instead of concentrating prefill grafts and
    active-lane occupancy on shard 0's lane block. Host-side only: the
    device step is oblivious to which lanes are preferred.
    """

    def __init__(self, max_lanes: int, lane_order: Optional[Sequence[int]] = None):
        assert max_lanes >= 1
        self.max_lanes = max_lanes
        self._pending: List[Request] = []
        self._keys: List[tuple] = []  # (arrival, seq) sort keys
        self._seq = 0
        self._lane_req: List[Optional[Request]] = [None] * max_lanes
        self._lane_state: List[Optional[str]] = [None] * max_lanes
        # chunked-prefill cursors: prompt tokens already written / total,
        # keyed by lane; ``_prefill_order`` keeps admission (FIFO) order
        # so the engine spends its per-step budget oldest-first
        self._prefill_cursor: Dict[int, int] = {}
        self._prefill_target: Dict[int, int] = {}
        self._prefill_order: List[int] = []
        order = list(range(max_lanes)) if lane_order is None else list(lane_order)
        assert sorted(order) == list(
            range(max_lanes)
        ), f"lane_order must permute 0..{max_lanes - 1}: {lane_order}"
        # stack: pop() assigns, so the preferred-first order goes reversed
        self._free: List[int] = order[::-1]

    # -- submission ----------------------------------------------------
    def submit(self, req: Request) -> None:
        key = (float(req.arrival), self._seq)
        i = bisect.bisect(self._keys, key)
        self._keys.insert(i, key)
        self._pending.insert(i, req)
        self._seq += 1

    # -- queries -------------------------------------------------------
    @property
    def has_pending(self) -> bool:
        return bool(self._pending)

    @property
    def has_work(self) -> bool:
        return bool(self._pending) or self.num_active > 0

    @property
    def num_active(self) -> int:
        return self.max_lanes - len(self._free)

    @property
    def num_decoding(self) -> int:
        return sum(1 for s in self._lane_state if s == LANE_DECODING)

    @property
    def num_prefilling(self) -> int:
        return len(self._prefill_order)

    @property
    def next_arrival(self) -> Optional[float]:
        return self._keys[0][0] if self._keys else None

    def request_in(self, lane: int) -> Request:
        req = self._lane_req[lane]
        assert req is not None, f"lane {lane} is free"
        return req

    def active_lanes(self) -> List[int]:
        return [i for i, r in enumerate(self._lane_req) if r is not None]

    def lane_state(self, lane: int) -> Optional[str]:
        return self._lane_state[lane]

    def decoding_lanes(self) -> List[int]:
        return [
            i for i, s in enumerate(self._lane_state) if s == LANE_DECODING
        ]

    def prefilling_lanes(self) -> List[int]:
        """Lanes with an in-flight chunked prefill, in admission order."""
        return list(self._prefill_order)

    # -- admission / retirement ---------------------------------------
    def can_admit(self, now: float) -> bool:
        """A lane is free and the queue head has arrived by ``now``."""
        return bool(self._free) and bool(self._keys) and self._keys[0][0] <= now

    def pop_admissible(self, now: float, skip: int = 0) -> Optional[Request]:
        """Pop the (``skip``+1)-th pending request that has arrived, if a
        lane is free. ``skip`` > 0 is the head-of-line lookahead: when the
        queue head cannot be admitted (page pool exhausted), the engine
        retries with increasing ``skip`` so later small requests are not
        blocked by a large head (first-fit within a bounded window)."""
        if not self._free or len(self._pending) <= skip:
            return None
        if self._keys[skip][0] > now:
            return None
        self._last_key = self._keys.pop(skip)
        return self._pending.pop(skip)

    def unpop(self, req: Request) -> None:
        """Return the most recently popped request to its exact previous
        queue position (admission resource check failed — e.g. the page
        pool can't fit it yet). Keys are unique, so bisect restores the
        original order among equal arrivals."""
        key = getattr(self, "_last_key", (float(req.arrival), -1))
        i = bisect.bisect_left(self._keys, key)
        self._keys.insert(i, key)
        self._pending.insert(i, req)

    def assign(self, req: Request, prefilling: bool = False) -> int:
        lane = self._free.pop()
        self._lane_req[lane] = req
        self._lane_state[lane] = LANE_PREFILLING if prefilling else LANE_DECODING
        if prefilling:
            self._prefill_cursor[lane] = 0
            self._prefill_target[lane] = req.prompt_len
            self._prefill_order.append(lane)
        return lane

    # -- chunked-prefill state machine --------------------------------
    def begin_prefill(self, lane: int, cursor: int, target: int) -> None:
        """Set the cursor window for a PREFILLING lane: ``cursor`` tokens
        already in the cache (a shared prefix), ``target`` total prompt
        tokens to reach."""
        assert self._lane_state[lane] == LANE_PREFILLING, lane
        assert 0 <= cursor < target, (cursor, target)
        self._prefill_cursor[lane] = cursor
        self._prefill_target[lane] = target

    def prefill_cursor(self, lane: int) -> int:
        return self._prefill_cursor[lane]

    def prefill_remaining(self, lane: int) -> int:
        return self._prefill_target[lane] - self._prefill_cursor[lane]

    def advance_prefill(self, lane: int, num_tokens: int) -> None:
        """Record ``num_tokens`` prompt tokens written by one chunk."""
        assert self._lane_state[lane] == LANE_PREFILLING, lane
        assert num_tokens >= 1, num_tokens
        cur = self._prefill_cursor[lane] + num_tokens
        assert cur <= self._prefill_target[lane], (cur, lane)
        self._prefill_cursor[lane] = cur

    def mark_decoding(self, lane: int) -> None:
        """PREFILLING -> DECODING transition (final chunk done, first
        token sampled). The cursor must have reached the prompt length."""
        assert self._lane_state[lane] == LANE_PREFILLING, lane
        assert self._prefill_cursor[lane] == self._prefill_target[lane], lane
        self._lane_state[lane] = LANE_DECODING
        self._prefill_cursor.pop(lane)
        self._prefill_target.pop(lane)
        self._prefill_order.remove(lane)

    def retire(self, lane: int) -> Request:
        req = self._lane_req[lane]
        assert req is not None, f"retiring free lane {lane}"
        assert (
            self._lane_state[lane] == LANE_DECODING
        ), f"retiring lane {lane} mid-prefill"
        self._lane_req[lane] = None
        self._lane_state[lane] = None
        self._free.append(lane)
        return req


class PagePool:
    """Host-side free-list allocator for the block-paged KV cache.

    Owns the workload-to-memory scheduling decisions the device never
    sees: which physical pages back each lane's page-table row, page
    refcounts (shared prefix pages are mapped read-only into several
    lanes), and the prefix index that detects page-aligned common prompt
    prefixes. The device side (repro.core.kvcache.PagedAttnCache) only
    ever receives finished page-table rows, so every jitted step stays
    static-shaped.

    Sharing contract: only *full* pages of a prompt are shareable, so the
    divergence point is always page-aligned and shared pages are never
    written by decode (private tail/decode pages start at the divergence
    page). ``make_private`` is the copy-on-write escape hatch for any
    future policy that would write inside a shared region.

    Invariants (property-tested in tests/test_kvcache_properties.py):
      * a physical page is mapped by at most one lane unless it is a
        registered shared-prefix page,
      * refcount == number of lanes mapping the page,
      * free pages are never referenced by any lane,
      * the free list and the mapped set partition the pool.
    """

    def __init__(self, num_pages: int, page_size: int, *, prefix_sharing: bool = True):
        assert num_pages >= 1 and page_size >= 1
        self.num_pages = num_pages
        self.page_size = page_size
        self.prefix_sharing = prefix_sharing
        self._free: List[int] = list(range(num_pages - 1, -1, -1))
        self.refcount = np.zeros((num_pages,), np.int64)
        self._lane_pages: Dict[int, List[int]] = {}
        # chain-hash of the full token prefix ending at each shared page
        self._prefix_index: Dict[bytes, int] = {}
        self._page_key: Dict[int, bytes] = {}
        # stats
        self.peak_in_use = 0
        self.prefix_hits = 0
        self.tokens_saved = 0
        self.util_sum = 0.0
        self.util_samples = 0

    # -- queries -------------------------------------------------------
    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def utilization(self) -> float:
        return self.pages_in_use / self.num_pages

    @property
    def mean_utilization(self) -> float:
        return self.util_sum / max(self.util_samples, 1)

    def sample_utilization(self) -> None:
        """Record one utilization sample (the engine calls this per
        decode step; the bench gate judges the mean)."""
        self.util_sum += self.utilization
        self.util_samples += 1

    def lane_pages(self, lane: int) -> List[int]:
        return list(self._lane_pages.get(lane, []))

    def can_reserve(self, num_new: int) -> bool:
        return num_new <= len(self._free)

    # -- prefix sharing ------------------------------------------------
    @staticmethod
    def _chain_digests(tokens, num_pages: int, page_size: int) -> List[bytes]:
        """Rolling chain digests, one per full page:
        ``digest_i = sha1(digest_{i-1} || page_i_tokens)``. Cumulative —
        two prompts share page ``i`` only when *all* earlier tokens match
        too — and computed in one O(prompt_len) pass (re-hashing the full
        prefix per page would be quadratic on the admission path)."""
        toks = np.asarray(tokens, np.int32)
        out: List[bytes] = []
        d = b"aqua-page-chain"
        for i in range(num_pages):
            page = np.ascontiguousarray(toks[i * page_size : (i + 1) * page_size])
            d = hashlib.sha1(d + page.tobytes()).digest()
            out.append(d)
        return out

    def lookup_prefix(self, tokens) -> List[int]:
        """Longest run of already-pooled full pages matching the prompt's
        page-aligned prefix. Returns their physical page ids in logical
        order (possibly empty)."""
        if not self.prefix_sharing:
            return []
        toks = np.asarray(tokens, np.int32)
        shared: List[int] = []
        for key in self._chain_digests(
            toks, len(toks) // self.page_size, self.page_size
        ):
            pid = self._prefix_index.get(key)
            if pid is None:
                break
            shared.append(pid)
        return shared

    def register_prefix(self, tokens, pages: Sequence[int], prompt_len: int) -> None:
        """Index the full pages covered by ``prompt_len`` of a freshly
        prefilled prompt for future sharing. First writer wins: an already
        indexed chain keeps its existing physical page."""
        if not self.prefix_sharing:
            return
        toks = np.asarray(tokens, np.int32)
        digests = self._chain_digests(
            toks, prompt_len // self.page_size, self.page_size
        )
        for i, key in enumerate(digests):
            if key in self._prefix_index:
                continue
            pid = pages[i]
            self._prefix_index[key] = pid
            self._page_key[pid] = key

    # -- reserve / release --------------------------------------------
    def reserve(
        self, lane: int, shared_pages: Sequence[int], num_new: int
    ) -> Optional[List[int]]:
        """Map ``shared_pages`` (increfed) plus ``num_new`` fresh pages
        into ``lane``. Returns the lane's full page list in logical order,
        or None (nothing changed) when the free list can't cover it."""
        assert lane not in self._lane_pages, f"lane {lane} already mapped"
        if num_new > len(self._free):
            return None
        fresh = [self._free.pop() for _ in range(num_new)]
        pages = list(shared_pages) + fresh
        for p in pages:
            self.refcount[p] += 1
        self._lane_pages[lane] = pages
        self.peak_in_use = max(self.peak_in_use, self.pages_in_use)
        return list(pages)  # snapshot: make_private may remap the lane

    def release(self, lane: int) -> None:
        """Unmap a retired lane: decref its pages; pages reaching
        refcount 0 return to the free list and leave the prefix index
        (freed pages are never referenced)."""
        for p in self._lane_pages.pop(lane, []):
            self.refcount[p] -= 1
            assert self.refcount[p] >= 0, f"page {p} refcount underflow"
            if self.refcount[p] == 0:
                key = self._page_key.pop(p, None)
                if key is not None:
                    self._prefix_index.pop(key, None)
                self._free.append(p)

    def make_private(self, lane: int, logical_page: int) -> Optional[Tuple[int, int]]:
        """Copy-on-write: give ``lane`` a private copy of its
        ``logical_page`` if that page is shared (refcount > 1). Returns
        ``(old_phys, new_phys)`` for the caller to copy device-side, or
        None when the page was already private (no copy needed). The
        fresh page is *not* prefix-indexed (its content will diverge)."""
        pages = self._lane_pages[lane]
        old = pages[logical_page]
        if self.refcount[old] <= 1:
            return None
        if not self._free:
            raise RuntimeError("page pool exhausted during copy-on-write")
        new = self._free.pop()
        self.refcount[old] -= 1
        self.refcount[new] += 1
        pages[logical_page] = new
        self.peak_in_use = max(self.peak_in_use, self.pages_in_use)
        return old, new


def poisson_trace(
    num_requests: int,
    *,
    mean_interarrival: float,
    prompt_lens: tuple,
    max_new_tokens: int,
    vocab_size: int,
    seed: int = 0,
    temperature: float = 0.0,
) -> List[Request]:
    """Synthetic mixed-traffic trace: Poisson arrivals (exponential
    inter-arrival times in decode-step units), prompt lengths cycled from
    ``prompt_lens``, random token prompts. Used by ``launch/serve.py``
    and the ``serving_throughput`` benchmark."""
    rng = np.random.default_rng(seed)
    reqs, t = [], 0.0
    for i in range(num_requests):
        t += float(rng.exponential(mean_interarrival))
        s = int(prompt_lens[i % len(prompt_lens)])
        toks = rng.integers(0, vocab_size, size=(s,), dtype=np.int32)
        reqs.append(
            Request(
                uid=i,
                tokens=toks,
                max_new_tokens=max_new_tokens,
                temperature=temperature,
                arrival=t,
            )
        )
    return reqs
