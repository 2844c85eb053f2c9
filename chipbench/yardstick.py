"""The benchmark's fixed measures: chip peaks, the compile clock, the chip
check, and the operations and bytes that the model and its AQUA kernels need.

Copied here so that a change to the program cannot move them:
- ``CHIP_PEAKS`` / ``chip_peaks`` from ``src/repro/launch/mesh.py``;
- ``CompileClock`` and ``tpu_devices`` from ``chip_smoke.py``;
- the per-token parameter count of ``benchmarks/roofline.py``, which each
  architecture module gives as ``Shapes.active``.

Every count is of what the algorithm needs, worked out from shapes and the
real context of each token, independent of how a kernel walks its grid.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Optional

BF16_BYTES = 2


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks (roofline denominators)."""

    bf16_flops: float     # FLOP/s
    hbm_bw: float         # bytes/s


# Keyed by ``jax.Device.device_kind``. Source: Google Cloud documentation,
# "TPU v5e" (system architecture): 197 TFLOP/s bf16 and 819 GB/s of HBM
# bandwidth per chip.
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, hbm_bw=819e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of ``device_kind``; an unknown kind raises rather
    than borrowing another chip's numbers."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}"
                       ) from None


class CompileClock:
    """Records every backend compile as (end time on the host clock,
    seconds). A persistent-cache hit shows as a much shorter compile."""

    def __init__(self, clock):
        import jax
        self._clock = clock
        self.compiles: list = []
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((self._clock(), secs))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def seconds(self) -> float:
        return sum(s for _, s in self.compiles)

    def count_between(self, t0: float, t1: float) -> int:
        """Compiles that ended inside [t0, t1] on the host clock."""
        return sum(1 for t, _ in self.compiles if t0 <= t <= t1)

    def seconds_between(self, t0: float, t1: float) -> float:
        return sum(s for t, s in self.compiles if t0 <= t <= t1)


def tpu_devices(chips: int):
    """The TPU devices, or exit non-zero without a result: no CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU (JAX found {devices[0].platform!r}); "
              "refusing to run elsewhere", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"chipbench: the cell needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


def device_peaks(devices) -> list:
    """``peak_bytes_in_use`` of each device's ``memory_stats()``, in the
    order given; None where the backend reports none."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def fullest(peaks: list) -> Optional[int]:
    """The largest of the devices' peaks: a cell is as large as its
    fullest device. None where no device reports one."""
    known = [p for p in peaks if p is not None]
    return max(known) if known else None


# ---------------------------------------------------------------------------
# Operations and bytes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Shapes:
    """The sizes the counts need. An architecture module builds them from
    its configuration file (``arch/<architecture>.py``, ``shapes``)."""

    active: int           # parameters a token multiplies: layers + unembedding
    unembed: int          # the unembedding's share of ``active``
    windows: tuple        # per attention layer: keys it may see, None = all
    heads: int            # query heads of an attention layer
    kv_heads: int
    head_dim: int
    k_dims: int           # dims the |q| selection keeps (score product)

    @property
    def layers(self) -> int:
        return len(self.windows)

    def decode_keys(self, context: float) -> float:
        """Keys one query over ``context`` keys attends, over all layers."""
        return sum(context if w is None else min(context, w)
                   for w in self.windows)

    def causal_keys(self, prompt: int) -> float:
        """Keys a causal prefill of ``prompt`` tokens attends (token i sees
        i + 1, or its window), over all layers."""
        def one(w):
            if w is None or prompt <= w:
                return prompt * (prompt + 1) / 2.0
            return w * (w + 1) / 2.0 + (prompt - w) * w
        return sum(one(w) for w in self.windows)


def round_k_dims(d: int, k_ratio: float, block_dims: int) -> int:
    """Dims kept by the selection: the nearest dim count, rounded up to
    whole blocks, clamped to [block_dims, d]."""
    k = max(block_dims, int(round(k_ratio * d)))
    k = -(-k // block_dims) * block_dims
    return min(k, d)


def attention_flops(s: Shapes, keys: float) -> float:
    """Attention FLOPs over ``keys`` keys summed over layers: the score
    product on the ``k_dims`` selected dims, the value product on all
    ``head_dim``."""
    return 2.0 * s.heads * keys * (s.k_dims + s.head_dim)


def decode_token_flops(s: Shapes, context: int) -> float:
    """Model FLOPs of one decoded token whose query sees ``context`` keys."""
    return 2.0 * s.active + attention_flops(s, s.decode_keys(context))


def prefill_flops(s: Shapes, prompt: int) -> float:
    """Model FLOPs of a prefill of ``prompt`` tokens: every token through
    the layers, the unembedding once (the first token's logits), and
    causal attention (token i sees i + 1 keys)."""
    return (2.0 * (s.active - s.unembed) * prompt + 2.0 * s.unembed
            + attention_flops(s, s.causal_keys(prompt)))


def aqua_decode_cost(s: Shapes, context: int) -> tuple:
    """(FLOPs, bytes) the AQUA decode kernel needs for one lane's query in
    every layer: each KV head's selected K-hat dims and its V over the real
    context, read once; q and the output written once, in bf16."""
    keys = s.decode_keys(context)
    kv = s.kv_heads * keys * (s.k_dims + s.head_dim)
    qo = 2 * s.heads * s.head_dim
    return (attention_flops(s, keys),
            float((kv + s.layers * qo) * BF16_BYTES))


def aqua_prefill_cost(s: Shapes, prompt: int) -> tuple:
    """(FLOPs, bytes) the AQUA prefill kernel needs for one prompt in every
    layer: causal half of the score and value products; q, selected K-hat,
    V and the output each moved once, in bf16."""
    flops = attention_flops(s, s.causal_keys(prompt))
    moved = prompt * (s.heads * (s.k_dims + s.head_dim)
                      + s.kv_heads * (s.k_dims + s.head_dim))
    return flops, float(s.layers * moved * BF16_BYTES)


def least_seconds(flops: float, nbytes: float, peaks: ChipPeaks) -> float:
    """Roofline time: the larger of operations over peak FLOP/s and bytes
    over peak bandwidth."""
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bw)
