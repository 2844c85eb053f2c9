"""Process-wide analysis + dispatch flags.

``UNROLL_SCANS``: XLA's HloCostAnalysis counts a while-loop body ONCE, not
×trip-count (verified empirically — see EXPERIMENTS.md §Roofline/method).
Production lowering uses lax.scan for flat HLO and fast compiles; the
roofline dry-run sets this flag so every scan (layer stack, microbatch
accumulation, chunked-attention blocks) lowers fully unrolled and
cost_analysis reports true FLOPs/bytes. Compile is slower; numbers are
honest. The multi-pod feasibility sweep keeps scans rolled.

Pallas dispatch flags (set before first jit; they are read at trace time):

``INTERPRET_OVERRIDE``: force Pallas interpret mode on (True) or off
(False). ``None`` auto-resolves: compiled on TPU, interpreted elsewhere —
so the exact same kernel code path runs compiled in production and
interpreted in CI.

``PALLAS_OVERRIDE``: force the attention backend registry's view of Pallas
availability. ``None`` = auto (available iff the pallas module imports);
``False`` simulates an install without Pallas (the registry then falls
back to the masked-dense jnp reference); ``True`` additionally makes the
``auto`` backend choice prefer the Pallas kernels even off-TPU (interpret
mode — useful for kernel-path testing on CPU).
"""

import os
from pathlib import Path

UNROLL_SCANS = False

# Analysis-only override for chunked-attention block sizes (q_blk, k_blk).
# Unrolling 32k/512 × 32k/1024 = 2048 blocks per layer stalls XLA passes;
# the roofline run uses larger blocks (identical FLOPs, same HBM-byte
# totals to first order, never executed) so the unrolled graph stays
# tractable. None = production sizes.
ATTN_BLOCK_OVERRIDE = None  # Optional[Tuple[int, int]]

INTERPRET_OVERRIDE = None   # Optional[bool]
PALLAS_OVERRIDE = None      # Optional[bool]


def scan_kwargs() -> dict:
    return {"unroll": True} if UNROLL_SCANS else {}


def attn_blocks(q_blk: int, k_blk: int):
    if ATTN_BLOCK_OVERRIDE is not None:
        return ATTN_BLOCK_OVERRIDE
    return q_blk, k_blk


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU. A backend that fails to
    initialize raises here: silently answering False would run every
    kernel interpreted and every ``auto`` choice on the jnp references."""
    import jax
    return jax.default_backend() == "tpu"


def pallas_available() -> bool:
    """Can a Pallas kernel run at all (compiled on TPU, else interpret)?"""
    if PALLAS_OVERRIDE is not None:
        return PALLAS_OVERRIDE
    try:
        from jax.experimental import pallas  # noqa: F401
    except ImportError:
        return False
    return True


def kernels_preferred() -> bool:
    """Should the ``auto`` backend choice pick Pallas kernels?

    Compiled kernels on TPU, jnp reference paths elsewhere — unless
    ``PALLAS_OVERRIDE`` forces the kernel (interpret) path for testing.
    """
    if not pallas_available():
        return False
    return on_tpu() or PALLAS_OVERRIDE is True


def resolve_interpret(interpret=None) -> bool:
    """Resolve a kernel's ``interpret`` argument: explicit value wins, then
    ``INTERPRET_OVERRIDE``, then auto-detect (compiled iff on TPU)."""
    if interpret is not None:
        return bool(interpret)
    if INTERPRET_OVERRIDE is not None:
        return bool(INTERPRET_OVERRIDE)
    return not on_tpu()


# Fixed in-checkout location of JAX's persistent compilation cache (the
# cache key includes the path, so it must not move between runs).
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself). Otherwise the cache lives at :data:`COMPILE_CACHE_DIR`, a
    fixed directory of the checkout listed in ``.gitignore``. Entry points
    (``launch/serve.py``, ``benchmarks/run.py``, ``chip_smoke.py``) call
    this once before their first compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
