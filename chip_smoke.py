"""Chip smoke test: the AQUA serving path on a TPU, end to end.

Run from the repository root on a machine with a TPU:

    python3 chip_smoke.py             # one chip: qwen3-0.6b serve + reference
    python3 chip_smoke.py --chips 4   # four chips: llama3.1-8b on a 1x4 mesh

One chip: serves about a dozen Poisson requests (prompts of 256-2048
tokens, 32 new tokens each) at qwen3-0.6b's published widths with bf16
weights drawn from ``--seed``, through ``repro.launch.serve.main`` — the
code ``python -m repro.launch.serve`` runs — with AQUA projections
calibrated on ``corpora/calibration.txt``, the ``aqua-block-sparse``
kernels (``k_ratio=0.75``, ``block_dims=8``) and a paged cache. It then
checks the first decode step's logits against the ``aqua-masked-dense``
reference computed in float32 (:func:`reference_check`).

``--chips 4``: runs only llama3.1-8b at full width on a 1x4 data x model
mesh — parameters initialized in their shards, the block-sparse kernels
shard_mapped — and the same logits comparison on that mesh.

Every phase runs in this one process. The timings printed are smoke
timings, not metrics. The last line of standard output is one JSON
object, ``{"ok": ..., "device": {"platform", "kind", "count"}}``; the
exit code is non-zero on any failure. Without a TPU it exits non-zero
and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

CORPUS = os.path.join(ROOT, "corpora", "calibration.txt")
ARTIFACTS = os.path.join(ROOT, ".artifacts")

# qwen3-0.6b serve phase: the options `python -m repro.launch.serve` gets.
SERVE_ARGS = (
    "--arch", "qwen3-0.6b", "--param-dtype", "bfloat16",
    "--backend", "aqua-block-sparse", "--k-ratio", "0.75",
    "--block-dims", "8", "--page-size", "128", "--lanes", "8",
    "--max-seq", "4096", "--requests", "12",
    "--prompt-lens", "256,512,1024,2048", "--steps", "32",
    "--calibration-corpus", CORPUS,
)


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache reads
    included, so a cache hit shows as a much shorter compile)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def tpu_devices():
    """The TPU devices, or exit non-zero without a result."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform!r}); "
              "refusing to run elsewhere", file=sys.stderr)
        raise SystemExit(2)
    return devices


def peak_bytes(device):
    """Peak device bytes in use, where the backend reports it."""
    stats = device.memory_stats()
    return stats["peak_bytes_in_use"] if stats else None


def serve_phase(seed: int, extra_args=()) -> dict:
    """Serve the trace through ``launch.serve.main`` and judge it: every
    request finished, the single-device plan is kernel-native (its only
    reason is the missing mesh) and no kernel fallback fired."""
    from repro.core.dispatch import REASON_NO_MESH
    from repro.launch import serve

    os.makedirs(ARTIFACTS, exist_ok=True)
    proj_path = os.path.join(ARTIFACTS, f"qwen3-0.6b-seed{seed}-proj.npz")
    argv = [*SERVE_ARGS, "--seed", str(seed), "--projections", proj_path,
            *extra_args]
    summary = serve.main(argv)
    eng, plan = summary["engine"], summary["plan"]
    summary["events"] = eng.mesh_fallback_events()
    summary["checks"] = {
        "all_finished": summary["finished"] == summary["requests"],
        "kernel_plan": plan.reasons == (REASON_NO_MESH,),
        "no_fallback": not summary["events"],
    }
    return summary


def _paged_kernel_logits(model, page_size: int, max_seq: int):
    """First-step logits of the served path: kernel prefill into a
    contiguous B-row cache, grafted into a paged state (identity page
    tables), then one paged-kernel decode step."""
    import jax
    import jax.numpy as jnp
    from repro.models.base import DecodeState, PagingSpec

    npl = max_seq // page_size

    def run(params, proj, tokens, lengths, next_tok):
        b, t = tokens.shape
        _, req = model.prefill(params, {"tokens": tokens, "lengths": lengths},
                               max_seq, aqua_proj=proj)
        model.enable_paging(PagingSpec(page_size, b * npl))
        state = model.init_decode_state(b, max_seq)
        table = jnp.arange(b * npl, dtype=jnp.int32).reshape(b, npl)
        layers = dataclasses.replace(state.layers, page_table=jnp.broadcast_to(
            table, state.layers.page_table.shape))
        state = DecodeState(layers=layers, extra=state.extra)
        for lane in range(b):
            one = jax.tree.map(lambda a: a[:, lane:lane + 1], req)
            state = model.graft_paged(state, one, lane, t)
        logits, _ = model.decode_step(params, state, next_tok,
                                      aqua_proj=proj)
        return logits
    return run


def _reference_logits(model, max_seq: int, select_q_blk: int):
    """First-step logits of the masked-dense reference. Each prompt
    prefills through the chunk step with the kernel's per-tile dim-block
    selection (``select_q_blk``), so both sides select the same blocks."""
    import jax.numpy as jnp

    def run(params, proj, tokens, lengths, next_tok):
        b = tokens.shape[0]
        state = model.init_decode_state(b, max_seq)
        for lane in range(b):
            batch = {"tokens": tokens[lane:lane + 1],
                     "lengths": lengths[lane:lane + 1]}
            _, state = model.prefill_chunk(params, batch, state, lane,
                                           jnp.int32(0), aqua_proj=proj,
                                           select_q_blk=select_q_blk)
        logits, _ = model.decode_step(params, state, next_tok,
                                      aqua_proj=proj)
        return logits
    return run


def reference_check(cfg, params, proj, prompts, next_tok, *,
                    page_size: int = 128, mesh=None) -> dict:
    """First decode step's logits, served path vs reference.

    ``cfg`` is the served configuration (``aqua-block-sparse``, bf16).
    Three programs see the same bf16 weights and prompts: the served
    kernel path, the ``aqua-masked-dense`` reference in float32 (matmuls
    at full precision), and the same reference in the served dtype. The
    last one measures what the dtype alone costs (``floor``). The kernel
    passes when its error against the float32 reference is at most twice
    that floor plus 1% of the largest reference logit: a kernel that
    reads wrong pages, blocks or rows is off by the logits' own scale.
    Under ``mesh`` every program runs on it with its kernels shard_mapped.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.attention import use_decode_mesh
    from repro.models import build_model

    t = max(len(p) for p in prompts)
    t = -(-t // page_size) * page_size
    tokens = np.zeros((len(prompts), t), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    lengths = np.asarray([len(p) for p in prompts], np.int32)
    max_seq = t + page_size
    ref_cfg = dataclasses.replace(cfg, attention=dataclasses.replace(
        cfg.attention, backend="aqua-masked-dense"))
    args = (params, proj, jnp.asarray(tokens), jnp.asarray(lengths),
            jnp.asarray(next_tok, jnp.int32))
    events: set = set()
    out = {}
    with use_decode_mesh(mesh, fallback_sink=events):
        out["kernel"] = jax.jit(_paged_kernel_logits(
            build_model(cfg), page_size, max_seq))(*args)
        out["bf16"] = jax.jit(_reference_logits(
            build_model(ref_cfg), max_seq, cfg.aqua.prefill_q_blk))(*args)
        with jax.default_matmul_precision("highest"):
            out["f32"] = jax.jit(_reference_logits(
                build_model(dataclasses.replace(ref_cfg, dtype="float32")),
                max_seq, cfg.aqua.prefill_q_blk))(*args)
    ref = np.asarray(out["f32"], np.float64)
    scale = float(np.max(np.abs(ref)))
    err = float(np.max(np.abs(np.asarray(out["kernel"], np.float64) - ref)))
    floor = float(np.max(np.abs(np.asarray(out["bf16"], np.float64) - ref)))
    tol = 2.0 * floor + 0.01 * scale
    finite = all(bool(np.all(np.isfinite(np.asarray(v)))) for v in
                 out.values())
    print(f"[smoke] reference check ({len(prompts)} prompts, lengths "
          f"{lengths.tolist()}): max|ref logit| {scale}, "
          f"kernel-vs-f32 max-abs error {err}, bf16-reference-vs-f32 "
          f"{floor}, tolerance {tol}")
    return {"err": err, "floor": floor, "tol": tol, "scale": scale,
            "events": tuple(sorted(events)),
            "ok": finite and err <= tol and not events}


def one_chip(seed: int, clock: CompileClock) -> bool:
    import jax

    dev = jax.devices()[0]
    c0, t0 = clock.seconds, time.perf_counter()
    summary = serve_phase(seed)
    wall = time.perf_counter() - t0
    print(f"[smoke] serve phase (smoke timings, not metrics): "
          f"{wall:.1f}s wall, {clock.seconds - c0:.1f}s compiling, "
          f"{summary['serve_s']:.1f}s in the serve loop, "
          f"{clock.cache_hits} compile-cache hits so far")
    print(f"[smoke] dispatch plan: {summary['plan']}")
    print(f"[smoke] fallback events: {list(summary['events'])}")
    print(f"[smoke] peak device bytes after serving: {peak_bytes(dev)}")
    print(f"[smoke] serve checks: {summary['checks']}")

    eng = summary["engine"]
    trace = summary["trace"]
    short = min(trace, key=lambda r: r.prompt_len)
    long = max(trace, key=lambda r: r.prompt_len)
    first = [summary["outputs"][r.uid][0] for r in (long, short)]
    eng.last_state = None              # free the page pool
    c0 = clock.seconds
    ref = reference_check(eng.cfg, eng.params, eng.proj,
                          [long.tokens, short.tokens], first)
    print(f"[smoke] reference phase: {clock.seconds - c0:.1f}s compiling; "
          f"peak device bytes {peak_bytes(dev)}")
    return all(summary["checks"].values()) and ref["ok"]


def four_chips(seed: int, clock: CompileClock, cfg=None,
               prompt_lens=(2048, 700)) -> bool:
    """llama3.1-8b (or ``cfg``) on a 1x4 data x model mesh: sharded
    initialization, then :func:`reference_check` on the mesh."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.configs.base import AquaConfig, CacheSpec, ServingConfig
    from repro.core.calibration import identity_projections
    from repro.core.dispatch import resolve_dispatch_plan
    from repro.launch.mesh import make_serving_mesh
    from repro.launch.serve import init_params
    from repro.models import build_model

    if cfg is None:
        cfg = get_config("llama3.1-8b")
    att = dataclasses.replace(cfg.attention, backend="aqua-block-sparse")
    cfg = dataclasses.replace(cfg, attention=att, param_dtype="bfloat16",
                              aqua=AquaConfig(k_ratio=0.75, block_dims=8))
    mesh = make_serving_mesh((1, 4))
    plan = resolve_dispatch_plan(
        attention=att, aqua=cfg.aqua, mesh=mesh,
        serving=ServingConfig(max_lanes=2, max_seq=4096,
                              cache=CacheSpec(page_size=128)))
    print(f"[smoke] mesh {dict(mesh.shape)}; dispatch plan: {plan}")

    c0, t0 = clock.seconds, time.perf_counter()
    params = jax.block_until_ready(init_params(build_model(cfg), seed, mesh))
    leaves = jax.tree.leaves(params)
    total = sum(x.nbytes for x in leaves)
    per_dev = {d.id: 0 for d in mesh.devices.flat}
    for x in leaves:
        for shard in x.addressable_shards:
            per_dev[shard.device.id] += shard.data.nbytes
    print(f"[smoke] {cfg.name}: {total} parameter bytes, initialized in "
          f"shards ({time.perf_counter() - t0:.1f}s wall); bytes per "
          f"device {per_dev}")

    # identity projections: AQUA selects over the raw head dims (the
    # kernel path under test is the same; calibration is the one-chip
    # phase's job)
    proj = identity_projections(cfg.num_layers, att.num_kv_heads,
                                att.head_dim).p
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,), dtype=np.int32)
               for n in prompt_lens]
    next_tok = rng.integers(0, cfg.vocab_size, size=(2,), dtype=np.int32)
    ref = reference_check(cfg, params, proj, prompts, next_tok, mesh=mesh)
    peaks = [peak_bytes(d) for d in mesh.devices.flat]
    print(f"[smoke] four-chip phase: {clock.seconds - c0:.1f}s compiling; "
          f"peak device bytes per device {peaks}")
    print(f"[smoke] kernel fallback events on the mesh: {list(ref['events'])}")
    return plan.mesh_native and ref["ok"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = tpu_devices()
    from repro import runtime_flags
    cache_dir = runtime_flags.configure_compile_cache()
    clock = CompileClock()
    print(f"[smoke] device: platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={len(devices)}; "
          f"compile cache {cache_dir}")
    if args.chips > len(devices):
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    phase = four_chips if args.chips == 4 else one_chip
    ok = phase(args.seed, clock)
    print(f"[smoke] total compile seconds {clock.seconds:.1f}, "
          f"compile-cache hits {clock.cache_hits}")
    print(json.dumps({"ok": bool(ok), "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
