import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST run before any jax import (jax locks the
# device count at first init). Everything else follows.

import argparse        # noqa: E402
import dataclasses     # noqa: E402
import json            # noqa: E402
import re              # noqa: E402
import sys             # noqa: E402
import time            # noqa: E402
from typing import Any, Dict, Optional, Tuple  # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp
from repro import runtime_flags as _rtf


def _scan(*args, **kw):
    kw.update(_rtf.scan_kwargs())
    return jax.lax.scan(*args, **kw)
  # noqa: E402
import numpy as np     # noqa: E402

from repro.configs import (ALL_ARCHS, ASSIGNED_ARCHS, AquaConfig,  # noqa: E402
                           SHAPES_BY_NAME, get_config)
from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig  # noqa: E402
from repro.distributed import sharding as sh  # noqa: E402
from repro.launch.mesh import chip_peaks, make_production_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import adamw  # noqa: E402

SDS = jax.ShapeDtypeStruct
# the production meshes this dry-run sizes are built from v5e chips
DRYRUN_DEVICE_KIND = "TPU v5 lite"


# ---------------------------------------------------------------------------
# input specs
# ---------------------------------------------------------------------------


def num_attn_layers(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        pat = cfg.rglru.block_pattern
        return sum(1 for i in range(cfg.num_layers)
                   if pat[i % len(pat)] == "attention")
    if cfg.family == "ssm":
        return 0
    return cfg.num_layers


def proj_spec(cfg: ModelConfig) -> Optional[SDS]:
    if cfg.aqua is None or not cfg.aqua.enabled or cfg.attention is None:
        return None
    la = num_attn_layers(cfg)
    if la == 0:
        return None
    d = cfg.attention.head_dim
    return SDS((la, cfg.attention.num_kv_heads, d, d), jnp.float32)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell
    (weak-type-correct, shardable, no device allocation)."""
    b, s = shape.global_batch, shape.seq_len
    model = build_model(cfg)
    specs: Dict[str, Any] = {}
    if shape.mode in ("train", "prefill"):
        batch = {"tokens": SDS((b, s), jnp.int32)}
        if shape.mode == "train":
            batch["labels"] = SDS((b, s), jnp.int32)
        if cfg.frontend.kind == "vision_patches":
            batch["patches"] = SDS(
                (b, cfg.frontend.num_embeds, cfg.frontend.embed_dim),
                jnp.float32)
        if cfg.family == "encdec":
            batch["frames"] = SDS((b, cfg.frontend.num_embeds, cfg.d_model),
                                  jnp.float32)
        specs["batch"] = batch
    else:  # decode
        specs["tokens"] = SDS((b,), jnp.int32)
        specs["state"] = jax.eval_shape(
            lambda: model.init_decode_state(b, s))
    specs["params"] = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ps = proj_spec(cfg)
    if ps is not None:
        specs["proj"] = ps
    return specs


# ---------------------------------------------------------------------------
# cell construction: (fn, ordered arg specs, in_shardings)
# ---------------------------------------------------------------------------


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               microbatches: int = 2):
    model = build_model(cfg)
    specs = input_specs(cfg, shape)
    params_sh = jax.tree_util.tree_map_with_path(
        lambda p, a: sh.NamedSharding(mesh, sh.param_pspec(p, a.shape, mesh)),
        specs["params"])
    proj = specs.get("proj")
    proj_sh = None
    if proj is not None:
        proj_sh = sh.NamedSharding(
            mesh, sh.sanitize(sh.P(None, "model", None, None),
                              proj.shape, mesh))

    if shape.mode == "train":
        tcfg = TrainConfig(microbatches=microbatches)

        def train_step(params, opt, batch):
            mb = tcfg.microbatches
            split = jax.tree.map(
                lambda a: a.reshape(mb, a.shape[0] // mb, *a.shape[1:]),
                batch)

            def acc_fn(carry, micro):
                loss_c, g_c = carry
                (l, _), g = jax.value_and_grad(
                    lambda p: model.loss(p, micro), has_aux=True)(params)
                return (loss_c + l / mb,
                        jax.tree.map(lambda a, b: a + b / mb, g_c, g)), None
            zero_g = jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32), params)
            (loss, grads), _ = _scan(acc_fn, (0.0, zero_g), split)
            new_params, new_opt = adamw.update(params, grads, opt, 1e-4, tcfg)
            return new_params, new_opt, loss

        opt_spec = jax.eval_shape(adamw.init, specs["params"])
        # ZeRO-1: Adam moments sharded over data axes on top of TP.
        opt_sh = jax.tree_util.tree_map_with_path(
            lambda p, a: sh.NamedSharding(
                mesh, sh.zero1_pspec(p, a.shape, mesh)), opt_spec)
        batch_sh = jax.tree.map(
            lambda a: sh.NamedSharding(mesh, sh.batch_pspec(mesh, a.shape)),
            specs["batch"])
        args = (specs["params"], opt_spec, specs["batch"])
        in_sh = (params_sh, opt_sh, batch_sh)
        out_sh = (params_sh, opt_sh, sh.NamedSharding(mesh, sh.P()))
        return train_step, args, in_sh, out_sh

    if shape.mode == "prefill":
        def prefill(params, batch, proj_arr=None):
            return model.prefill(params, batch, shape.seq_len,
                                 aqua_proj=proj_arr)
        batch_sh = jax.tree.map(
            lambda a: sh.NamedSharding(mesh, sh.batch_pspec(mesh, a.shape)),
            specs["batch"])
        args = [specs["params"], specs["batch"]]
        in_sh = [params_sh, batch_sh]
        if proj is not None:
            args.append(proj)
            in_sh.append(proj_sh)
        return prefill, tuple(args), tuple(in_sh), None

    # decode
    kvh = cfg.attention.num_kv_heads if cfg.attention is not None else 0
    state_sh = sh.make_state_shardings(specs["state"], mesh, kv_heads=kvh,
                                       batch=shape.global_batch)

    def decode(params, state, tokens, proj_arr=None):
        return model.decode_step(params, state, tokens, aqua_proj=proj_arr)

    tok_sh = sh.NamedSharding(
        mesh, sh.batch_pspec(mesh, (shape.global_batch,)))
    args = [specs["params"], specs["state"], specs["tokens"]]
    in_sh = [params_sh, state_sh, tok_sh]
    if proj is not None:
        args.append(proj)
        in_sh.append(proj_sh)
    return decode, tuple(args), tuple(in_sh), None


# ---------------------------------------------------------------------------
# HLO collective-byte accounting
# ---------------------------------------------------------------------------

_COLL_RE = re.compile(
    r"\b(f64|f32|bf16|f16|s64|s32|s16|s8|u64|u32|u16|u8|pred)"
    r"\[([0-9,]*)\][^=]*\b"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "s32": 4,
                "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2, "u8": 1,
                "pred": 1}


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-buffer bytes of every collective op in optimized HLO."""
    out: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        dt, dims, op = m.group(1), m.group(2), m.group(3)
        size = 1
        if dims:
            for d in dims.split(","):
                size *= int(d)
        nbytes = size * _DTYPE_BYTES[dt]
        out[op] = out.get(op, 0) + nbytes
    return out


# ---------------------------------------------------------------------------
# run one cell
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             aqua: Optional[str] = "auto", verbose: bool = True,
             seq_parallel: bool = True, donate: bool = True,
             unroll: bool = False) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]

    skip_reason = None
    if (shape_name == "long_500k" and cfg.skip_long_context
            and aqua not in ("h2o", "memory")):
        # AQUA-H2O budgets make dense 500k decode cache-feasible — run
        # those cells explicitly with --aqua h2o (beyond-paper extras).
        skip_reason = "pure full attention: quadratic prefill / unbounded " \
                      "cache at 500k (DESIGN.md §4)"
    if skip_reason:
        return {"arch": arch, "shape": shape_name, "skipped": skip_reason}

    # AQUA policy: serve cells of attention archs use the paper operating
    # point (k_ratio=0.75) unless told otherwise; train cells use standard
    # attention (AQUA is an inference technique).
    use_aqua = (aqua in ("on", "h2o", "memory") or
                (aqua == "auto" and shape.is_serve
                 and cfg.attention is not None))
    use_aqua = use_aqua and cfg.attention is not None
    if use_aqua:
        if aqua == "h2o":
            # heavy-hitter budget = 6.25% of context (32k slots at 500k)
            cfg = cfg.with_aqua(AquaConfig(k_ratio=0.75, h2o_ratio=0.0625,
                                           block_dims=8))
        elif aqua == "memory":
            cfg = cfg.with_aqua(AquaConfig(k_ratio=0.75, s_ratio=0.25,
                                           block_dims=8))
        else:
            cfg = cfg.with_aqua(AquaConfig(k_ratio=0.75, block_dims=8))

    # honest loop accounting for the roofline (see runtime_flags docstring)
    _rtf.UNROLL_SCANS = unroll
    blk_env = os.environ.get("REPRO_ANALYSIS_BLOCKS")
    if blk_env:
        _rtf.ATTN_BLOCK_OVERRIDE = tuple(int(x) for x in blk_env.split(","))
    else:
        _rtf.ATTN_BLOCK_OVERRIDE = (4096, 8192) if unroll else None

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))

    def measure(c: ModelConfig, microbatches: int = 2) -> Dict[str, Any]:
        if seq_parallel and shape.mode in ("train", "prefill"):
            sh.set_activation_sharding(sh.make_seq_parallel_sharding(
                mesh, shape.global_batch, shape.seq_len))
        else:
            sh.set_activation_sharding(None)
        if c.family == "hybrid" and shape.mode in ("train", "prefill"):
            w = c.rglru.lru_width or c.d_model
            sh.set_lru_gate_sharding(sh.make_width_sharding(
                mesh, shape.global_batch, w))
        else:
            sh.set_lru_gate_sharding(None)
        fn, args, in_sh, out_sh = build_cell(c, shape, mesh, microbatches)
        t0 = time.time()
        jit_kw: Dict[str, Any] = {}
        if donate and shape.mode == "train":
            jit_kw["donate_argnums"] = (0, 1)   # params, opt state
        elif donate and shape.mode == "decode":
            jit_kw["donate_argnums"] = (1,)     # decode state
        try:
            with mesh:
                jitted = jax.jit(fn, in_shardings=in_sh,
                                 out_shardings=out_sh, **jit_kw)
                lowered = jitted.lower(*args)
                t_l = time.time() - t0
                compiled = lowered.compile()
                t_c = time.time() - t0 - t_l
        finally:
            sh.set_activation_sharding(None)
            sh.set_lru_gate_sharding(None)
        cost = compiled.cost_analysis() or {}
        try:
            mem = compiled.memory_analysis()
            mem_d = {
                "bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
                "argument_bytes": getattr(mem, "argument_size_in_bytes",
                                          None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            }
        except Exception as e:  # CPU backend may not implement it
            mem_d = {"error": str(e)}
        coll = collective_bytes(compiled.as_text())
        return {"flops": float(cost.get("flops", 0.0)),
                "bytes": float(cost.get("bytes accessed", 0.0)),
                "coll": coll, "mem": mem_d,
                "lower_s": round(t_l, 1), "compile_s": round(t_c, 1)}

    method = "full"
    if unroll and shape.mode in ("train", "prefill") and cfg.num_layers > 6:
        # Exact layer extrapolation: layers are homogeneous, so
        # X(L) = X(n1) + (L - n1)/(n2 - n1) * (X(n2) - X(n1)) holds for
        # FLOPs / bytes / collective bytes. Compiling two shallow unrolled
        # variants is minutes instead of hours at depth 24-48.
        if cfg.family == "hybrid":
            unit = len(cfg.rglru.block_pattern)
            n1, n2 = unit, 2 * unit
        else:
            n1, n2 = 1, 2
        mb = 1  # microbatching doesn't change per-step totals
        m1 = measure(dataclasses.replace(cfg, num_layers=n1), mb)
        m2 = measure(dataclasses.replace(cfg, num_layers=n2), mb)
        scale = (cfg.num_layers - n1) / (n2 - n1)

        def extrap(a, b):
            return a + scale * (b - a)
        coll_keys = set(m1["coll"]) | set(m2["coll"])
        mres = {
            "flops": extrap(m1["flops"], m2["flops"]),
            "bytes": extrap(m1["bytes"], m2["bytes"]),
            "coll": {k: extrap(m1["coll"].get(k, 0), m2["coll"].get(k, 0))
                     for k in coll_keys},
            # memory feasibility comes from the rolled sweep, not this run
            "mem": {"note": "see rolled (non-unroll) sweep for peak memory"},
            "lower_s": m1["lower_s"] + m2["lower_s"],
            "compile_s": m1["compile_s"] + m2["compile_s"],
        }
        method = f"layer-extrapolated({n1},{n2})"
    else:
        mres = measure(cfg, 1 if unroll else 2)

    mem_d = mres["mem"]
    coll = mres["coll"]
    flops = mres["flops"]
    bytes_acc = mres["bytes"]
    t_lower, t_compile = mres["lower_s"], mres["compile_s"]
    coll_total = float(sum(coll.values()))
    # roofline terms: XLA's cost_analysis on the SPMD-partitioned module is
    # PER-PARTITION (verified against a hand-sharded matmul), i.e. already
    # per-chip work — divide only by per-chip capability.
    peaks = chip_peaks(DRYRUN_DEVICE_KIND)
    t_compute = flops / peaks.bf16_flops
    t_memory = bytes_acc / peaks.hbm_bw
    t_coll = coll_total / peaks.ici_link_bw

    res = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(v) for v in mesh.shape.values()),
        "mode": shape.mode, "aqua": bool(use_aqua), "chips": chips,
        "hlo_flops": flops, "hlo_bytes": bytes_acc,
        "collective_bytes": coll, "collective_total": coll_total,
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "bottleneck": max((("compute", t_compute), ("memory", t_memory),
                           ("collective", t_coll)), key=lambda kv: kv[1])[0],
        "memory_analysis": mem_d, "method": method,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    }
    if verbose:
        print(json.dumps(res, indent=2, default=str))
    return res


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--aqua", default="auto",
                    choices=["auto", "on", "off", "h2o", "memory"])
    ap.add_argument("--no-seq-parallel", action="store_true",
                    help="disable activation sequence parallelism (for "
                         "before/after perf comparison)")
    ap.add_argument("--no-donate", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll every scan so cost_analysis reports true "
                         "FLOPs/bytes (roofline runs; slower compile)")
    ap.add_argument("--sweep", action="store_true",
                    help="all (arch x shape) cells")
    ap.add_argument("--out", default=None, help="JSONL output path")
    args = ap.parse_args()

    cells = []
    if args.sweep:
        for arch in ASSIGNED_ARCHS:
            for sname in ("train_4k", "prefill_32k", "decode_32k",
                          "long_500k"):
                cells.append((arch, sname))
    else:
        assert args.arch and args.shape, "--arch/--shape or --sweep"
        cells = [(args.arch, args.shape)]

    results = []
    for arch, sname in cells:
        print(f"=== {arch} × {sname} "
              f"({'multi-pod' if args.multi_pod else 'single-pod'}) ===",
              flush=True)
        try:
            res = run_cell(arch, sname, multi_pod=args.multi_pod,
                           aqua=args.aqua,
                           seq_parallel=not args.no_seq_parallel,
                           donate=not args.no_donate, unroll=args.unroll)
        except Exception as e:
            res = {"arch": arch, "shape": sname, "error": repr(e)[:500]}
            print("FAILED:", res["error"], flush=True)
        results.append(res)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(res, default=str) + "\n")
    n_err = sum(1 for r in results if "error" in r)
    print(f"\n{len(results)} cells, {n_err} errors")
    sys.exit(1 if n_err else 0)


if __name__ == "__main__":
    main()
