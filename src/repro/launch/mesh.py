"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state.
"""
from __future__ import annotations

import dataclasses
import math

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips (16, 16) = (data, model).
    Multi-pod: 512 chips (2, 16, 16) = (pod, data, model); the "pod" axis
    is pure data parallelism across the DCN/ICI-pod boundary."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the production axis names (CPU tests)."""
    return jax.make_mesh((1, 1), ("data", "model"))


def make_serving_mesh(shape, axes=("data", "model")):
    """Mesh over the first prod(shape) available devices (serving engine).

    Unlike the fixed production meshes above, serving meshes come from the
    ``ServingConfig`` / ``--mesh`` flag and must work on whatever devices
    exist — 8 forced host-platform CPU devices in CI, a TPU slice in
    production. Raises with the CPU fake-device recipe when the platform
    has too few devices.
    """
    import numpy as np

    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"mesh {tuple(shape)} needs {n} devices, found {len(devs)}; "
            "on CPU, launch with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}")
    return jax.sharding.Mesh(
        np.asarray(devs[:n]).reshape(tuple(shape)), tuple(axes))


def parse_mesh_spec(spec: str):
    """Parse a ``--mesh`` CLI value: "4x2" -> ((4, 2), (data, model));
    "2x2x2" -> ((2, 2, 2), (pod, data, model)); "4" -> ((4, 1),
    (data, model)) — pure data parallelism keeps a singleton model axis,
    since the sharding rules address ``model`` by name; "1x1"/"" -> None
    (single-device serving, no mesh)."""
    if not spec:
        return None
    shape = tuple(int(x) for x in spec.lower().split("x"))
    if math.prod(shape) == 1:
        return None
    if len(shape) == 1:
        shape = (shape[0], 1)
    axes = {2: ("data", "model"), 3: ("pod", "data", "model")}.get(len(shape))
    if axes is None:
        raise ValueError(f"--mesh {spec!r}: expected 1-3 'x'-separated dims")
    return shape, axes


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks (roofline denominators)."""

    bf16_flops: float     # FLOP/s
    hbm_bw: float         # bytes/s
    ici_link_bw: float    # bytes/s per interconnect link


# Keyed by ``jax.Device.device_kind``. Source: Google Cloud documentation,
# "TPU v5e" (system architecture): 197 TFLOP/s bf16, 819 GB/s of HBM
# bandwidth, 1,600 Gbit/s of interconnect per chip over four links
# (50 GB/s each).
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, hbm_bw=819e9,
                             ici_link_bw=50e9),
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
