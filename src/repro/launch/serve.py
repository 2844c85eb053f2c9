"""Serving driver: calibrate-once, then serve a mixed-traffic trace.

Drives the continuous-batching engine over a Poisson arrival trace
(exponential inter-arrival times in decode-step units, mixed prompt
lengths) and reports throughput + lane occupancy. ``--rectangular``
falls back to the old fixed-batch ``ServeEngine`` drive for comparison.

``--mesh DxM`` serves mesh-native on a data×model device mesh (decode
lanes data-parallel, params/KV cache tensor-parallel; Pallas backends
run shard_mapped when the axis extents divide the mesh); ``--verify``
re-serves the same trace single-device and asserts token-identical
outputs (the multi-device CI acceptance check) — and, when a Pallas
backend should serve shard_mapped, additionally asserts that no mesh
kernel fallback fired (the kernel path really ran on the mesh).

CLI (CPU-scale):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --k-ratio 0.75 --h2o-ratio 0.5 --requests 8 --lanes 4

  # 4x2 data×model mesh on 8 forced host devices, verified vs 1-device
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --requests 8 --lanes 8 --mesh 4x2 --verify
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import numpy as np

import jax

from repro import runtime_flags
from repro.configs import get_config, reduced
from repro.configs.base import (AquaConfig, CacheSpec, QuantSpec,
                                ServingConfig, SparsitySpec)
from repro.core.calibration import calibrate, identity_projections
from repro.data.pipeline import DataConfig, add_frontend_inputs, \
    calibration_batches, make_batch
from repro.models import build_model
from repro.serving import ContinuousBatchingEngine, ServeEngine, \
    poisson_trace, telemetry


def init_params(model, seed: int, mesh=None):
    """Random parameters from ``seed``. Under a mesh every leaf is created
    in its own shard (``jax.jit`` with the parameter shardings as
    ``out_shardings``), so no device ever holds the whole model."""
    key = jax.random.PRNGKey(seed)
    if mesh is None:
        return model.init(key)
    from repro.distributed import sharding as dsh
    shardings = dsh.make_param_shardings(jax.eval_shape(model.init, key),
                                         mesh)
    return jax.jit(model.init, out_shardings=shardings)(key)


def main(argv=None) -> dict:
    """Serve one trace; returns a summary (engine, dispatch plan, the
    trace, request and token counts, serve wall seconds, streamed tokens
    per uid)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--hf-checkpoint", default=None,
                    help="serve real weights: path to an HF-format "
                         "safetensors checkpoint dir (config.json + "
                         "model.safetensors[.index.json]); overrides "
                         "--arch/--reduced — the architecture is read "
                         "from config.json (see checkpoint.hf)")
    ap.add_argument("--calibration-corpus", default=None,
                    help="tokenized corpus file for the offline SVD "
                         "calibration (.npy/.npz ids or .txt byte-level; "
                         "see data.pipeline.load_token_corpus); default "
                         "is the synthetic LCG language")
    ap.add_argument("--projections", default=None,
                    help="AquaProjections .npz artifact path: load it if "
                         "it exists, else calibrate and save there "
                         "(skip recalibration across serve runs)")
    ap.add_argument("--k-ratio", type=float, default=0.75)
    ap.add_argument("--s-ratio", type=float, default=0.0)
    ap.add_argument("--h2o-ratio", type=float, default=1.0)
    ap.add_argument("--block-dims", type=int, default=1)
    ap.add_argument("--prefill-q-blk", type=int, default=None,
                    help="block-sparse prefill kernel q-chunk tile (one "
                         "dim-block selection per tile); a chunked-prefill "
                         "budget must be a multiple of it")
    ap.add_argument("--param-dtype", default=None,
                    choices=("float32", "bfloat16"),
                    help="parameter dtype (default: the config's)")
    ap.add_argument("--no-aqua", action="store_true")
    ap.add_argument("--backend", default=None,
                    help="attention backend override (see core.attention)")
    # trace shape
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--mean-interarrival", type=float, default=2.0,
                    help="Poisson trace: mean inter-arrival (decode steps)")
    ap.add_argument("--prompt-lens", default="8,16,24",
                    help="comma-separated mixed prompt lengths")
    ap.add_argument("--steps", type=int, default=16,
                    help="max new tokens per request")
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and of the trace")
    ap.add_argument("--rectangular", action="store_true",
                    help="old fixed-batch ServeEngine drive (comparison)")
    # block-paged KV cache
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV-cache page: replaces per-lane "
                         "contiguous slot stripes with a global page pool "
                         "+ per-lane page tables (None = contiguous)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="page-pool size; default = lane-stripe parity "
                         "(lanes * slots / page_size) — set lower to "
                         "realize the HBM win (admissions then queue on "
                         "free pages)")
    ap.add_argument("--no-prefix-share", action="store_true",
                    help="disable prompt prefix page sharing")
    ap.add_argument("--kv-dtype", default="bf16", choices=("bf16", "int8"),
                    help="paged K̂/V pool storage dtype: 'int8' stores "
                         "per-page symmetric-quantized pools with f32 "
                         "scale metadata beside the page table (requires "
                         "--page-size); decode folds the scales into the "
                         "Pallas kernel's softmax scale — no dequant pass")
    ap.add_argument("--scale-granularity", default="page_head",
                    choices=("page_head", "page"),
                    help="int8 scale granularity: one scale per "
                         "(page, kv head) or one per page")
    ap.add_argument("--hot-frac", type=float, default=0.0,
                    help="fraction of the pool kept as full-precision hot "
                         "residents (H2O score policy; mixed precision "
                         "serves on the reference path, not the kernel)")
    # hierarchical (two-stage) token sparsity
    ap.add_argument("--page-keep-ratio", type=float, default=1.0,
                    help="hierarchical AQUA: fraction of each lane's pages "
                         "participating in decode attention (stage-1 "
                         "page-granular token sparsity ranked by H2O page "
                         "mass; stage 2 is the |q̂| dim-block top-k). "
                         "Requires --page-size; 1.0 = every page (exactly "
                         "the plain paged kernel)")
    ap.add_argument("--pin-recent-pages", type=int, default=2,
                    help="hierarchical: trailing pages per lane always "
                         "participating (probe token + local window stay "
                         "exact)")
    # chunked-prefill/decode interleaving
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="interleave admissions with decode: at most this "
                         "many prefill tokens run between consecutive "
                         "decode steps (None = monolithic admission; the "
                         "engine falls back with an attributed reason when "
                         "the geometry/policy can't chunk — see "
                         "dispatch_plan().chunked_reasons)")
    ap.add_argument("--itl-slo-ms", type=float, default=None,
                    help="report the fraction of inter-token gaps above "
                         "this wall-clock threshold (SLO miss rate)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="prepend a fixed random prefix of this length to "
                         "every trace prompt (prefix-sharing demo/CI)")
    ap.add_argument("--mesh", default="",
                    help="serving mesh 'DATAxMODEL' (e.g. 4x2) or "
                         "'PODxDATAxMODEL'; empty/1x1 = single device")
    ap.add_argument("--verify", action="store_true",
                    help="re-serve the trace single-device and require "
                         "token-identical outputs (exits 1 on mismatch)")
    ap.add_argument("--expect-kernel-mesh", action="store_true",
                    help="require the shard_mapped Pallas kernel path: fail "
                         "unless the engine dispatches the block-sparse "
                         "kernels natively on the mesh (guards the CI "
                         "acceptance drive against a dispatch-predicate "
                         "regression silently serving the jnp reference)")
    args = ap.parse_args(argv)

    if args.hf_checkpoint is not None:
        from repro.checkpoint.hf import config_from_hf, load_hf_checkpoint
        cfg = config_from_hf(args.hf_checkpoint)
        print(f"[serve] HF checkpoint {args.hf_checkpoint}: "
              f"{cfg.name} ({cfg.num_layers}L d{cfg.d_model})")
    else:
        cfg = reduced(args.arch) if args.reduced else get_config(args.arch)
    aqua = None
    if not args.no_aqua and cfg.attention is not None:
        aqua = AquaConfig(k_ratio=args.k_ratio, s_ratio=args.s_ratio,
                          h2o_ratio=args.h2o_ratio,
                          block_dims=args.block_dims)
        if args.prefill_q_blk is not None:
            aqua = dataclasses.replace(aqua,
                                       prefill_q_blk=args.prefill_q_blk)
    cfg = dataclasses.replace(cfg, aqua=aqua)
    if args.param_dtype is not None:
        cfg = dataclasses.replace(cfg, param_dtype=args.param_dtype)

    from repro.launch.mesh import make_serving_mesh, parse_mesh_spec
    mesh_spec = parse_mesh_spec(args.mesh)
    mesh = None
    if mesh_spec is not None and not args.rectangular:
        mesh = make_serving_mesh(*mesh_spec)
        print(f"[serve] mesh {dict(mesh.shape)} over {mesh.size} "
              f"{mesh.devices.flat[0].platform} devices")

    model = build_model(cfg)
    if args.hf_checkpoint is not None:
        params = load_hf_checkpoint(args.hf_checkpoint, cfg)
    else:
        params = init_params(model, args.seed, mesh)

    proj = None
    if aqua is not None and args.projections is not None \
            and os.path.exists(args.projections):
        from repro.core.calibration import load_projections
        proj = load_projections(args.projections)
        print(f"[serve] loaded AQUA projections from {args.projections}")
    elif aqua is not None:
        src = args.calibration_corpus or "synthetic LCG"
        print(f"[serve] offline AQUA calibration for {cfg.name} "
              f"(corpus: {src}) ...")
        if cfg.family == "hybrid":
            # capture path collects only attention layers
            n_attn = model.num_attn_layers
            proj = identity_projections(n_attn, cfg.attention.num_kv_heads,
                                        cfg.attention.head_dim)

        def fwd_cap(p, batch):
            _, aux = model.forward(p, batch, capture=True)
            return aux
        proj = calibrate(fwd_cap, params,
                         calibration_batches(
                             cfg, num_batches=2, batch=2, seq=32,
                             corpus_path=args.calibration_corpus),
                         cfg) \
            if cfg.family != "hybrid" else proj
        if args.projections is not None:
            from repro.core.calibration import save_projections
            save_projections(args.projections, proj)
            print(f"[serve] saved AQUA projections to {args.projections}")

    if args.rectangular:
        return _drive_rectangular(cfg, params, proj, args)

    scfg = ServingConfig(max_lanes=args.lanes, max_seq=args.max_seq,
                         max_new_tokens=args.steps,
                         temperature=args.temperature,
                         prefill_budget_tokens=args.prefill_budget,
                         cache=CacheSpec(
                             page_size=args.page_size,
                             num_pages=args.pool_pages,
                             prefix_sharing=not args.no_prefix_share),
                         quant=QuantSpec(
                             kv_dtype=args.kv_dtype,
                             scale_granularity=args.scale_granularity,
                             hot_resident_fraction=args.hot_frac),
                         sparsity=SparsitySpec(
                             page_keep_ratio=args.page_keep_ratio,
                             pin_recent_pages=args.pin_recent_pages))
    eng = ContinuousBatchingEngine(cfg, params, proj, serving=scfg,
                                   backend=args.backend, mesh=mesh)
    plan = eng.dispatch_plan()
    print(f"[serve] dispatch plan: backend={plan.backend} "
          f"layout={plan.cache_layout} quantization={plan.quantization} "
          f"mesh_native={plan.mesh_native} reasons={list(plan.reasons)}")
    if args.prefill_budget is not None and not plan.chunked_prefill:
        print("[serve] chunked prefill OFF (monolithic admission): "
              f"{'; '.join(plan.chunked_reasons)}")
        if args.verify:
            # CI drives a budget to pin the interleaved path; a predicate
            # regression silently serving monolithic must fail loudly
            print("[serve] VERIFY FAILED: --prefill-budget requested but "
                  "the engine planned monolithic admission")
            raise SystemExit(1)
    if args.page_keep_ratio < 1.0 and plan.token_sparsity != "hierarchical":
        print("[serve] hierarchical token sparsity OFF (all pages "
              f"participate): {'; '.join(plan.token_reasons)}")
        if args.verify:
            # CI pins the hierarchical path with a ratio; a predicate
            # regression silently attending every page must fail loudly
            print("[serve] VERIFY FAILED: --page-keep-ratio requested but "
                  "the engine planned full page participation")
            raise SystemExit(1)
    if args.expect_kernel_mesh and not plan.mesh_native:
        # independent of the engine's own dispatch decision: the caller
        # (CI) declares the kernel path is REQUIRED for this geometry, so
        # a predicate regression fails loudly instead of silently serving
        # the masked-dense reference
        print("[serve] EXPECT-KERNEL FAILED: engine did not plan the "
              "kernel-native mesh path "
              f"(backend={plan.backend!r} layout={plan.cache_layout}); "
              f"reasons: {'; '.join(plan.reasons)}")
        raise SystemExit(1)
    prompt_lens = tuple(int(x) for x in args.prompt_lens.split(","))
    reqs = poisson_trace(args.requests,
                         mean_interarrival=args.mean_interarrival,
                         prompt_lens=prompt_lens,
                         max_new_tokens=args.steps,
                         vocab_size=cfg.vocab_size, seed=args.seed,
                         temperature=args.temperature)
    if args.shared_prefix_len > 0:
        pre = np.random.default_rng(args.seed + 1).integers(
            0, cfg.vocab_size, size=(args.shared_prefix_len,),
            dtype=np.int32)
        for r in reqs:
            r.tokens = np.concatenate([pre, np.asarray(r.tokens, np.int32)])
    if cfg.frontend.kind != "none":
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=1,
                          global_batch=1)
        for r in reqs:
            r.extra_inputs = {
                k: v for k, v in add_frontend_inputs(
                    {"tokens": make_batch(dcfg, 0)["tokens"]}, cfg).items()
                if k != "tokens"}

    t0 = time.time()
    mark = time.perf_counter_ns()
    finished = 0
    streamed: dict = {}
    for ev in eng.serve(reqs):
        streamed.setdefault(ev.uid, []).append(ev.token)
        if ev.finished:
            finished += 1
            print(f"[serve] request {ev.uid} done: {ev.index + 1} tokens "
                  f"({ev.finish_reason})")
    dt = time.time() - t0
    st = eng.stats
    spans = telemetry.summary([s for s in telemetry.spans()
                               if s.start_ns >= mark])
    print(f"[serve] engine spans (mean): step {spans['step']:.2f}ms "
          f"(wait {spans['wait']:.2f}ms), admit {spans['admit']:.2f}ms, "
          f"host gap {spans['host_gap']:.2f}ms")
    print(f"[serve] {finished}/{len(reqs)} requests, "
          f"{st.tokens_emitted} tokens in {dt:.2f}s "
          f"({st.tokens_emitted / dt:.1f} tok/s), "
          f"{st.decode_steps} decode steps, "
          f"mean lane occupancy {st.mean_occupancy:.2f}/{args.lanes}")
    if st.itl_gaps:
        line = (f"[serve] inter-token latency: p50 "
                f"{st.itl_percentile(50) * 1e3:.1f}ms, p99 "
                f"{st.itl_percentile(99) * 1e3:.1f}ms, max "
                f"{st.max_itl * 1e3:.1f}ms")
        if args.itl_slo_ms is not None:
            line += (f", SLO>{args.itl_slo_ms:g}ms miss rate "
                     f"{st.slo_miss_rate(args.itl_slo_ms / 1e3):.3f}")
        print(line)
    if args.prefill_budget is not None and plan.chunked_prefill:
        print(f"[serve] chunked prefill: {st.chunked_admissions} admissions "
              f"interleaved over {st.prefill_chunks} chunk steps "
              f"(budget {args.prefill_budget} tokens/step)")
    print(f"[serve] KV cache bytes @ {args.lanes} lanes: "
          f"{eng.cache_bytes():,}")
    if eng.paged:
        from repro.serving.engine import decode_state_bytes
        pool = eng.page_pool
        num_pages, per_lane, ps = eng.pool_geometry
        stripe_bytes = decode_state_bytes(build_model(cfg), args.lanes,
                                          args.max_seq)
        ratio = eng.cache_bytes() / stripe_bytes
        print(f"[serve] page pool: {num_pages} pages x {ps} tokens "
              f"(lane-stripe parity {per_lane * args.lanes}), "
              f"peak {pool.peak_in_use} in use, "
              f"mean utilization {pool.mean_utilization:.2f}")
        print(f"[serve] prefix sharing: {pool.prefix_hits} admissions "
              f"reused a shared prefix, {pool.tokens_saved} prefill "
              f"tokens saved")
        print(f"[serve] pool bytes vs lane-stripe bytes: "
              f"{eng.cache_bytes():,} / {stripe_bytes:,} = {ratio:.2f}x")
        if args.verify and num_pages < per_lane * args.lanes \
                and eng.cache_bytes() >= stripe_bytes:
            print("[serve] VERIFY FAILED: paged pool is smaller than "
                  "lane-stripe parity but does not report fewer cache "
                  "bytes")
            raise SystemExit(1)
        if (args.verify and args.shared_prefix_len > 0
                and not args.no_prefix_share and args.requests >= 2
                and pool.prefix_hits < 1):
            print("[serve] VERIFY FAILED: every prompt carries the same "
                  f"{args.shared_prefix_len}-token prefix but no "
                  "admission reused shared prefix pages")
            raise SystemExit(1)
        if eng.kept_pages is not None:
            kp, npl = eng.kept_pages, per_lane
            print(f"[serve] hierarchical: {kp}/{npl} pages per lane "
                  f"participate in decode (keep ratio "
                  f"{args.page_keep_ratio:g}, {args.pin_recent_pages} "
                  "recent pinned)")
            # numpy page-ranking oracle vs the jit stage-1 selection on
            # the terminal engine state — --verify pins that the table the
            # kernels scalar-prefetched is the one the reference ranking
            # math produces
            if args.verify:
                import jax as _jax
                from repro.core import kvcache as kvc
                from repro.core import selection
                stacked = [x for x in _jax.tree_util.tree_leaves(
                    eng.last_state,
                    is_leaf=lambda t: isinstance(t, kvc.PagedAttnCache))
                    if isinstance(x, kvc.PagedAttnCache)]
                # model decode state stacks layers into one cache (leading
                # L axis on every leaf); unstack to per-layer views
                caches = []
                for c in stacked:
                    if c.page_table.ndim == 2:
                        caches.append(c)
                        continue
                    for li in range(c.page_table.shape[0]):
                        caches.append((c.acc_pool[li], c.page_table[li],
                                       c.count[li]))
                bad_oracle = 0
                for c in caches:
                    acc, table, count = (
                        (c.acc_pool, c.page_table, c.count)
                        if isinstance(c, kvc.PagedAttnCache) else c)
                    got = np.asarray(selection.participating_pages(
                        acc, table, count,
                        page_size=ps, kept_pages=kp,
                        pin_recent_pages=args.pin_recent_pages))
                    want = selection.reference_participating_pages(
                        acc, table, count,
                        page_size=ps, kept_pages=kp,
                        pin_recent_pages=args.pin_recent_pages)
                    bad_oracle += int(not np.array_equal(got, want))
                if bad_oracle:
                    print(f"[serve] VERIFY FAILED: jit page ranking "
                          f"diverges from the numpy oracle on "
                          f"{bad_oracle}/{len(caches)} layer caches")
                    raise SystemExit(1)
                print(f"[serve] verify: page-ranking oracle agrees on all "
                      f"{len(caches)} layer caches")
        if eng.quant_spec.quantized:
            from repro.models.base import PagingSpec
            fp_model = build_model(cfg)
            fp_model.enable_paging(PagingSpec(ps, num_pages))
            fp_bytes = decode_state_bytes(fp_model, args.lanes,
                                          args.max_seq)
            qratio = eng.cache_bytes() / fp_bytes
            print(f"[serve] quantized pool ({eng.quant_spec.kv_dtype}) "
                  f"bytes vs full-precision paged: {eng.cache_bytes():,} "
                  f"/ {fp_bytes:,} = {qratio:.2f}x")
            if args.verify and qratio >= 0.60:
                print("[serve] VERIFY FAILED: quantized pool does not "
                      "realize the memory win (expected <= 0.60x the "
                      "full-precision paged pool)")
                raise SystemExit(1)

    if ((args.verify or args.expect_kernel_mesh) and mesh is not None
            and plan.mesh_native):
        # kernel-path identity is only meaningful if the kernel actually
        # served on the mesh. `plan.mesh_native` is the engine's resolved
        # dispatch decision (backend resolves to the block-sparse kernel,
        # AQUA block + page geometry + mesh extents admit it, no
        # H2O/window policy in the way) — --expect-kernel-mesh above
        # already failed if that decision itself went wrong — so any
        # per-engine fallback event means the masked-dense reference
        # silently served instead.
        backend_name = eng.cfg.attention.backend
        events = eng.mesh_fallback_events()
        if events:
            print(f"[serve] VERIFY FAILED: backend {backend_name!r} should "
                  f"serve shard_mapped on this mesh but fell back: {events}")
            raise SystemExit(1)
        print(f"[serve] verify: backend {backend_name!r} served shard_mapped "
              "on the mesh (no kernel fallback)")

    if args.verify:
        if mesh is not None:
            # the single-device reference engines below take their own
            # copy of the weights on one device
            params = jax.device_put(params, jax.devices()[0])
        # Token-identity reference. At greedy (temperature 0) the trace
        # re-serves on a fresh SINGLE-DEVICE engine — cross-partitioning
        # equality only holds there, since resharding the model axis
        # reorders float reductions and Gumbel sampling amplifies ulp
        # differences. At temperature > 0 each request instead re-serves
        # SOLO on a fresh same-mesh engine (empty lanes, arrival 0): that
        # checks the placement/co-tenant independence the (uid, counter)
        # RNG fold guarantees, and would catch e.g. a key folded on the
        # lane index — a batched same-trace rerun would not.
        if args.temperature > 0:
            where = "solo same-mesh"
            ref = {}
            for r in reqs:
                solo_eng = ContinuousBatchingEngine(
                    cfg, params, proj, serving=scfg, backend=args.backend,
                    mesh=mesh)
                ref.update(solo_eng.run(
                    [dataclasses.replace(r, arrival=0.0)]))
        else:
            # greedy: the reference is single-device AND contiguous, so a
            # paged drive is checked against the lane-stripe layout it
            # replaces (token-identity is exact — the gathered lane view
            # is slot-for-slot the contiguous cache). Exception: when
            # prefix sharing actually engages, a shared admission prefills
            # only its *tail* (attention.prefixed_tail_attention) — a
            # different reduction split than the contiguous engine's full
            # prompt prefill. The jnp backends reduce identically either
            # way, but a kernel-native engine full-prefills through the
            # Pallas prefill kernel, so shared-tail logits move by ulps
            # and greedy tokens can flip. Kernel-native prefix drives
            # therefore verify against the single-device *paged* engine
            # instead: the same admission paths solo, so the mesh wrap —
            # which is what --verify pins here — must be token-exact.
            # Quantized drives route the same way for a different reason:
            # int8 pools round differently than a full-precision cache by
            # construction, so only the single-device engine with the SAME
            # quantization math is a token-exact reference.
            # Hierarchical drives route like quantized ones: dropping
            # pages changes outputs vs exact attention by construction, so
            # only the single-device engine with the SAME page-ranking
            # math (scfg carries the SparsitySpec) is token-exact.
            prefix_engaged = (plan.prefix_sharing and plan.mesh_native
                              and args.shared_prefix_len > 0)
            if (prefix_engaged or plan.quantization != "none"
                    or plan.token_sparsity != "none"):
                where = ("single-device paged"
                         if plan.quantization == "none"
                         else f"single-device paged {plan.quantization}")
                if plan.token_sparsity != "none":
                    where += " hierarchical"
                ref_scfg = scfg
            else:
                where = "single-device contiguous"
                ref_scfg = dataclasses.replace(scfg, cache=CacheSpec(),
                                               quant=QuantSpec())
            # the reference always admits monolithically: a chunked drive
            # is thereby pinned against the engine it replaces — chunking
            # must change *when* work happens, never *what* is computed
            ref_scfg = dataclasses.replace(ref_scfg,
                                           prefill_budget_tokens=None)
            if args.prefill_budget is not None:
                where += " monolithic-admit"
            ref_eng = ContinuousBatchingEngine(cfg, params, proj,
                                               serving=ref_scfg,
                                               backend=args.backend)
            ref = ref_eng.run(reqs)
        bad = [uid for uid, toks in streamed.items()
               if list(ref[uid].tokens) != toks]
        if bad:
            print(f"[serve] VERIFY FAILED: outputs diverge from the "
                  f"{where} reference for uids {bad}")
            raise SystemExit(1)
        print(f"[serve] verify: all {len(streamed)} requests "
              f"token-identical to the {where} reference engine")
        if (args.prefill_budget is not None and plan.chunked_prefill
                and args.temperature == 0):
            # the point of interleaving: decode lanes never stall for a
            # whole co-tenant prefill, so the worst inter-token gap must
            # come down vs the monolithic-admit reference on the same
            # trace. Both engines re-serve WARM (every jit shape was
            # compiled by the drives above) — the first drives' gaps are
            # dominated by compilation, which the chunked engine pays
            # more of (one extra jit per chunk geometry), not by the
            # admission stalls this check pins.
            eng.run([dataclasses.replace(r) for r in reqs])
            ref_eng.run([dataclasses.replace(r) for r in reqs])
            warm_max = eng.stats.max_itl
            ref_max = ref_eng.stats.max_itl
            if warm_max >= ref_max and ref_max > 0:
                print(f"[serve] VERIFY FAILED: chunked max inter-token gap "
                      f"{warm_max * 1e3:.1f}ms is not below the "
                      f"monolithic reference's {ref_max * 1e3:.1f}ms "
                      "(warm re-drives)")
                raise SystemExit(1)
            print(f"[serve] verify: max inter-token gap "
                  f"{warm_max * 1e3:.1f}ms < monolithic "
                  f"{ref_max * 1e3:.1f}ms (warm re-drives)")
    return {"engine": eng, "plan": plan, "trace": reqs,
            "requests": len(reqs), "finished": finished,
            "tokens": st.tokens_emitted, "serve_s": dt, "outputs": streamed}


def _drive_rectangular(cfg, params, proj, args):
    """Old fixed-batch drive: every request prefills together and decodes
    in lockstep — no overlap, occupancy == 1 request-batch at a time."""
    eng = ServeEngine(cfg, params, proj, max_seq=args.max_seq,
                      backend=args.backend)
    batch_size = min(args.requests, args.lanes)
    prompt_len = int(args.prompt_lens.split(",")[0])
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=prompt_len,
                      global_batch=batch_size)
    batch = add_frontend_inputs(
        {"tokens": make_batch(dcfg, 0)["tokens"]}, cfg)
    t0 = time.time()
    res = eng.generate(batch, steps=args.steps,
                       temperature=args.temperature)
    dt = time.time() - t0
    tps = batch_size * args.steps / dt
    print(f"[serve] rectangular: generated {res.tokens.shape} tokens in "
          f"{dt:.2f}s ({tps:.1f} tok/s)")
    print(f"[serve] KV cache bytes @ batch={batch_size}: "
          f"{eng.cache_bytes(batch_size):,}")
    print("[serve] sample:", np.asarray(res.tokens[0])[:16].tolist())
    return {"engine": eng, "requests": batch_size,
            "finished": batch_size, "tokens": int(res.tokens.size),
            "serve_s": dt}


if __name__ == "__main__":
    runtime_flags.configure_compile_cache()
    main()
