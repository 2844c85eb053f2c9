"""DispatchPlan resolution (repro.core.dispatch) and its consumers.

The plan is the single source for the serving dispatch decision: these
tests pin the field values per scenario, the REASON_* vocabulary against
the trace-time fallback sink's keys, the chunked-prefill admissibility
predicate, and the README dispatch matrix (generated by
``repro.launch.matrix``) against the committed block.
"""
import dataclasses

import jax
import pytest
from jax.sharding import AbstractMesh

from repro.configs.base import (AquaConfig, AttentionConfig, CacheSpec,
                                QuantSpec, ServingConfig)
from repro.core.dispatch import (CACHE_CONTIGUOUS, CACHE_PAGED,
                                 REASON_CHUNK_GEOMETRY,
                                 REASON_FAMILY_SURGERY, REASON_FRONTEND,
                                 REASON_H2O, REASON_MOE_CAPACITY,
                                 REASON_NO_MESH, REASON_NO_PREFILL_BUDGET,
                                 REASON_NONDIVISIBLE_MESH,
                                 REASON_PAGE_GEOMETRY,
                                 REASON_PER_DIM_SELECTION,
                                 REASON_QUANT_GEOMETRY,
                                 REASON_QUANT_RESIDENCY,
                                 REASON_REFERENCE_BACKEND, REASON_WINDOW,
                                 DispatchPlan, resolve_dispatch_plan)

MESH = AbstractMesh((2, 2), ("data", "model"))
ATT = AttentionConfig(num_heads=4, num_kv_heads=2, head_dim=16,
                      backend="aqua-block-sparse")
AQUA = AquaConfig(k_ratio=0.5, block_dims=8)
SERVING = ServingConfig(max_lanes=4, max_seq=64)


def _plan(att=ATT, aqua=AQUA, serving=SERVING, mesh=MESH, **kw):
    return resolve_dispatch_plan(attention=att, aqua=aqua, serving=serving,
                                 mesh=mesh, **kw)


def test_contiguous_mesh_native():
    plan = _plan()
    assert plan == DispatchPlan(
        backend="aqua-block-sparse", cache_layout=CACHE_CONTIGUOUS,
        mesh_native=True, prefix_sharing=False, reasons=(),
        chunked_prefill=False,
        chunked_reasons=(REASON_NO_PREFILL_BUDGET,))
    assert not plan.paged


def test_paged_mesh_native():
    plan = _plan(serving=dataclasses.replace(
        SERVING, cache=CacheSpec(page_size=8)), prefix_sharing=True)
    assert plan.mesh_native and plan.paged and plan.prefix_sharing
    assert plan.cache_layout == CACHE_PAGED
    assert plan.reasons == ()
    assert plan.quantization == "none"


def test_no_mesh_reason():
    plan = _plan(mesh=None)
    assert not plan.mesh_native
    assert REASON_NO_MESH in plan.reasons


def test_reference_backend_reason():
    for backend in ("dense-jnp", "flash", "aqua-masked-dense"):
        att = dataclasses.replace(ATT, backend=backend)
        aqua = AquaConfig(k_ratio=0.75, block_dims=1) \
            if backend.startswith("aqua") else None
        plan = _plan(att=att, aqua=aqua)
        assert not plan.mesh_native, backend
        assert REASON_REFERENCE_BACKEND in plan.reasons, backend


def test_per_dim_selection_reason():
    plan = _plan(aqua=AquaConfig(k_ratio=0.5, block_dims=1))
    # block_dims=1 resolves aqua-block-sparse away from the kernel
    # backend entirely (per-dim selection has masked-dense semantics)
    assert not plan.mesh_native
    assert (REASON_PER_DIM_SELECTION in plan.reasons
            or REASON_REFERENCE_BACKEND in plan.reasons)


def test_policy_reasons():
    win = dataclasses.replace(ATT, window=32)
    plan = _plan(att=win)
    assert REASON_WINDOW in plan.reasons
    h2o = AquaConfig(k_ratio=0.5, block_dims=8, h2o_ratio=0.5)
    plan = _plan(aqua=h2o)
    assert REASON_H2O in plan.reasons


def test_nondivisible_and_page_geometry_reasons():
    plan = _plan(serving=dataclasses.replace(SERVING, max_lanes=3))
    assert plan.reasons == (REASON_NONDIVISIBLE_MESH,), plan
    plan = _plan(serving=dataclasses.replace(SERVING,
                                             cache=CacheSpec(page_size=4)))
    assert plan.reasons == (REASON_PAGE_GEOMETRY,), plan
    # divisible paged geometry with a non-divisible batch still reports
    # the axis reason, not the page one
    plan = _plan(serving=dataclasses.replace(SERVING, max_lanes=3,
                                             cache=CacheSpec(page_size=8)))
    assert plan.reasons == (REASON_NONDIVISIBLE_MESH,), plan


def test_quantization_field_and_reasons():
    """int8 pools stay kernel-native (the scale-folded path); mixed
    precision and reference-backend combinations get their own
    attribution in the REASON_* vocabulary."""
    qs = dataclasses.replace(SERVING, cache=CacheSpec(page_size=8),
                             quant=QuantSpec(kv_dtype="int8"))
    plan = _plan(serving=qs)
    assert plan.mesh_native and plan.paged
    assert plan.quantization == "int8"
    assert plan.reasons == ()
    # hot residents read through the reference path's dequantized view
    qm = dataclasses.replace(
        qs, quant=QuantSpec(kv_dtype="int8", hot_resident_fraction=0.25))
    plan = _plan(serving=qm)
    assert not plan.mesh_native
    assert plan.reasons == (REASON_QUANT_RESIDENCY,)
    assert plan.quantization == "int8-mixed"
    # a reference backend over quantized pools additionally attributes
    # the quant-geometry fallback (the dequantized lane view serves)
    att = dataclasses.replace(ATT, backend="aqua-masked-dense")
    plan = _plan(att=att, aqua=AquaConfig(k_ratio=0.75, block_dims=1),
                 serving=qs)
    assert not plan.mesh_native
    assert plan.reasons[-1] == REASON_QUANT_GEOMETRY


def test_cache_spec_is_the_only_non_deprecated_surface():
    """Flat ServingConfig cache fields are one-release shims: setting
    them warns at engine-style resolution, setting them alongside
    cache= fails, int8 without paging fails."""
    import warnings

    from repro.configs.base import resolve_cache_specs

    legacy = dataclasses.replace(SERVING, page_size=8, num_pages=16)
    with pytest.warns(DeprecationWarning, match="CacheSpec"):
        cache, quant = resolve_cache_specs(legacy, warn=True)
    assert cache == CacheSpec(page_size=8, num_pages=16)
    assert quant == QuantSpec()
    # validate()/dispatch resolution stays silent (warn=False)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        legacy.validate()
        _plan(serving=legacy)
    with pytest.raises(ValueError, match="both cache=CacheSpec"):
        resolve_cache_specs(dataclasses.replace(
            SERVING, page_size=8, cache=CacheSpec(page_size=8)), warn=False)
    with pytest.raises(ValueError, match="paged"):
        resolve_cache_specs(dataclasses.replace(
            SERVING, quant=QuantSpec(kv_dtype="int8")), warn=False)


def test_plan_is_frozen():
    plan = _plan()
    with pytest.raises(dataclasses.FrozenInstanceError):
        plan.mesh_native = False


def test_chunked_prefill_predicate():
    """``chunked_prefill`` flips on exactly when a budget is configured
    and no policy/family predicate forbids chunk-invariant prefill."""
    budget = dataclasses.replace(SERVING, prefill_budget_tokens=16)
    # budgets on the block-sparse backend must land on q-chunk tile
    # boundaries, so align the kernel tile with the 16-token budget
    aqua16 = dataclasses.replace(AQUA, prefill_q_blk=16)
    plan = _plan(aqua=aqua16, serving=budget)
    assert plan.chunked_prefill and plan.chunked_reasons == ()
    # no budget -> attributed, not silently off
    plan = _plan()
    assert not plan.chunked_prefill
    assert plan.chunked_reasons == (REASON_NO_PREFILL_BUDGET,)
    # a budget that straddles the kernel's q-chunk tile would change the
    # per-tile |q̂| aggregation -> monolithic admission, attributed
    plan = _plan(serving=budget)
    assert not plan.chunked_prefill
    assert plan.chunked_reasons == (REASON_CHUNK_GEOMETRY,)
    # policy exclusions ride the same REASON_* vocabulary
    plan = _plan(att=dataclasses.replace(ATT, window=32), aqua=aqua16,
                 serving=budget)
    assert REASON_WINDOW in plan.chunked_reasons
    plan = _plan(aqua=AquaConfig(k_ratio=0.5, block_dims=8, h2o_ratio=0.5,
                                 prefill_q_blk=16),
                 serving=budget)
    assert REASON_H2O in plan.chunked_reasons
    # family/frontend exclusions: capacity-routed MoE and
    # embedding-splicing frontends can't promise chunk invariance
    plan = _plan(aqua=aqua16, serving=budget, family="moe")
    assert plan.chunked_reasons == (REASON_MOE_CAPACITY,)
    plan = _plan(aqua=aqua16, serving=budget, family="mamba2")
    assert plan.chunked_reasons == (REASON_FAMILY_SURGERY,)
    plan = _plan(aqua=aqua16, serving=budget, frontend="vision_patches")
    assert plan.chunked_reasons == (REASON_FRONTEND,)


def test_engine_kernel_native_shim_is_gone():
    """The deprecated ``kernel_native`` property (PR 6 kept it one
    release) is removed; ``dispatch_plan().mesh_native`` is the API."""
    from repro.configs import reduced
    from repro.models import build_model
    from repro.serving import ContinuousBatchingEngine

    cfg = dataclasses.replace(reduced("qwen3-0.6b"), remat=False,
                              dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(cfg, params, None,
                                   serving=ServingConfig(max_lanes=2,
                                                         max_seq=32),
                                   backend="dense-jnp")
    plan = eng.dispatch_plan()
    assert not plan.mesh_native and REASON_NO_MESH in plan.reasons
    assert "kernel_native" not in type(eng).__dict__


def test_readme_matrix_is_golden():
    """The committed README dispatch matrix equals the generator's output
    — regenerate with `python -m repro.launch.matrix --readme README.md`."""
    import pathlib

    from repro.launch import matrix

    readme = pathlib.Path(__file__).resolve().parent.parent / "README.md"
    text = readme.read_text()
    begin, end = text.find(matrix.BEGIN), text.find(matrix.END)
    assert begin != -1 and end != -1, "README lost its matrix markers"
    committed = text[begin:end + len(matrix.END)]
    assert committed == matrix.generate_matrix()
    # embed() is idempotent on a fresh README
    assert matrix.embed(text) == text
