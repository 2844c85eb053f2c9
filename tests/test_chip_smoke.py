"""CPU rehearsal of chip_smoke.py's phases at reduced width.

The phases run here with interpret-mode kernels; the tests steer them to
a small size through the phase functions' own arguments (the script's
command line has no such option). On the chip the script runs them at
published widths.
"""
import dataclasses
import importlib.util
import os

import numpy as np

from repro.configs import reduced
from repro.core.dispatch import REASON_NO_MESH

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = ("--reduced", "--max-seq", "256", "--lanes", "2", "--requests", "3",
         "--prompt-lens", "24,40", "--steps", "3")


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_plans_kernel_and_never_falls_back(tmp_path, monkeypatch):
    smoke = _smoke()
    monkeypatch.setattr(smoke, "ARTIFACTS", str(tmp_path))
    summary = smoke.serve_phase(0, SMALL)
    assert summary["engine"].cfg.aqua.block_dims == 8
    assert summary["plan"].backend == "aqua-block-sparse"
    assert summary["plan"].reasons == (REASON_NO_MESH,)
    assert summary["events"] == ()
    assert summary["finished"] == summary["requests"] == 3
    assert all(summary["checks"].values()), summary["checks"]
    # the calibrated projections were written where the next run finds them
    assert os.listdir(tmp_path) == ["qwen3-0.6b-seed0-proj.npz"]

    # reference phase on the served engine, in the served dtype
    eng = summary["engine"]
    cfg = dataclasses.replace(eng.cfg, dtype="bfloat16")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,), dtype=np.int32)
               for n in (40, 24)]
    ref = smoke.reference_check(cfg, eng.params, eng.proj, prompts, [1, 2])
    assert ref["ok"], ref
    assert 0 < ref["err"] <= ref["tol"]


def test_four_chip_phase_on_host_devices():
    smoke = _smoke()
    base = reduced("llama3.1-8b")
    # four KV heads, so `model` (4) divides them and the kernels shard_map
    cfg = dataclasses.replace(base, attention=dataclasses.replace(
        base.attention, num_heads=8, num_kv_heads=4, head_dim=16))
    assert smoke.four_chips(0, smoke.CompileClock(), cfg=cfg,
                            prompt_lens=(40, 24))


def test_compile_cache_placement(monkeypatch, tmp_path):
    import jax

    from repro import runtime_flags

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        # set from outside: used as is, nothing else configured
        assert runtime_flags.configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = str(runtime_flags.COMPILE_CACHE_DIR)
        assert runtime_flags.configure_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert fixed == os.path.join(ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
