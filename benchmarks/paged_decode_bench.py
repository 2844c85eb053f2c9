"""Paged AQUA decode attention alone on a TPU, against a float32 reference.

Times ``repro.kernels.ops.aqua_paged_decode`` (selection, the q mask or
any relayout, and the kernel: everything one layer's paged decode runs)
at the benchmark cells' shapes and at geometries no cell runs: one query
head per KV head (G = 1, as qwen1.5-4b), int8 pools with per-page and
per-(page, KV head) scales, and hierarchical pages. Run from the
repository root on a machine with a TPU:

    python3 benchmarks/paged_decode_bench.py [--src DIR] [--reps N]

``--src DIR`` puts another checkout's ``src`` first on the import path,
so two commits are timed on the same chip by the same script (the call
uses only arguments both sides of such a comparison accept).

Each case builds ``LAYERS`` distinct pools (no two calls share an input,
so the compiler can neither merge nor hoist a call's work) and times one
program that runs the ``LAYERS`` calls, dispatched ``--reps`` times back
to back. ``ms_per_call`` is the wall time over reps × ``LAYERS``.
``max_abs_err`` compares layer 0's output with a float32 reference
written here: magnitude top-k of q's dim-blocks, each lane's pages
gathered through its table, softmax over the positions below its length
(and, for hierarchical pages, on its participating pages only); int8
pools are dequantized for the reference. One JSON line per case; the
last line holds them all. Exits non-zero without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LAYERS = 4
PAGE, D, K_RATIO, BD = 128, 128, 0.75, 8

# name: (lanes, pages per lane, query heads, KV heads, pool dtype,
#        scale heads (int8 only: KV or 1), participating pages or None)
CASES = {
    "long": (4, 80, 16, 8, "bfloat16", None, None),
    "short": (32, 10, 16, 8, "bfloat16", None, None),
    "g1_long": (4, 80, 20, 20, "bfloat16", None, None),
    "int8_page_head_long": (4, 80, 16, 8, "int8", 8, None),
    "int8_page_long": (4, 80, 16, 8, "int8", 1, None),
    "hier_long": (4, 80, 16, 8, "bfloat16", None, 20),
}


def lane_lengths(np, rng, lanes, lane_pages):
    """Lengths of the long-decode cell's lanes (4-8k prompts plus up to
    2k generated) or of short-chat's (128-1,024 plus up to 256), cut to
    the lane's pages."""
    cap = lane_pages * PAGE
    if lane_pages >= 40:
        lengths = rng.randint(4096, cap, lanes)
    else:
        lengths = rng.randint(144, cap, lanes)
    return np.minimum(lengths, cap).astype(np.int32)


def build(case, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    lanes, npl, h, kvh, dtype, sh, kept = CASES[case]
    rng = np.random.RandomState(seed % 2**32)
    lengths = lane_lengths(np, rng, lanes, npl)
    pool = lanes * npl
    used = -(-lengths // PAGE)
    table = rng.permutation(pool).reshape(lanes, npl).astype(np.int32)
    table[np.arange(npl)[None, :] >= used[:, None]] = -1
    part = None
    if kept is not None:
        # each lane's participating logical pages, sorted, the last two
        # it holds always among them
        rows = []
        for u in used:
            pinned = set(range(max(0, u - 2), u))
            rest = rng.permutation([i for i in range(npl) if i not in pinned])
            rows.append(sorted(list(pinned) + list(rest[:kept - len(pinned)])))
        part = jnp.asarray(np.asarray(rows, np.int32))
    key = jax.random.PRNGKey(seed % 2**31)
    kq, *kl = jax.random.split(key, 1 + 4 * LAYERS)
    q = jax.random.normal(kq, (LAYERS, lanes, h, D), jnp.bfloat16)
    layers = []
    for i in range(LAYERS):
        k1, k2, k3, k4 = kl[4 * i:4 * i + 4]
        shape = (pool, kvh, PAGE, D)
        if dtype == "int8":
            k = jax.random.randint(k1, shape, -127, 128, jnp.int8)
            v = jax.random.randint(k2, shape, -127, 128, jnp.int8)
            ks = jax.random.uniform(k3, (pool, sh), jnp.float32,
                                    0.5, 1.5) / 127.0
            vs = jax.random.uniform(k4, (pool, sh), jnp.float32,
                                    0.5, 1.5) / 127.0
        else:
            k = jax.random.normal(k1, shape, jnp.bfloat16)
            v = jax.random.normal(k2, shape, jnp.bfloat16)
            ks = vs = None
        layers.append((k, v, ks, vs))
    return (q, layers, jnp.asarray(table), jnp.asarray(lengths), part,
            int(lengths.sum()))


def reference(q, k, v, ks, vs, table, lengths, part):
    """float32 masked-dense AQUA decode over one layer's pages."""
    import jax
    import jax.numpy as jnp
    b, h, d = q.shape
    kvh = k.shape[1]
    nb = d // BD
    nb_sel = max(1, round(K_RATIO * d) // BD)
    qf = q.astype(jnp.float32)
    mag = jnp.abs(qf).reshape(b, h, nb, BD).sum(-1)
    order = jnp.argsort(-mag, axis=-1)[..., :nb_sel]
    keep = jnp.zeros((b, h, nb)).at[
        jnp.arange(b)[:, None, None], jnp.arange(h)[None, :, None],
        order].set(1.0)
    qm = (qf * jnp.repeat(keep, BD, axis=-1)).reshape(b, kvh, h // kvh, d)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    if ks is not None:
        kf = kf * jnp.repeat(ks, kvh // ks.shape[1], axis=1)[..., None, None]
        vf = vf * jnp.repeat(vs, kvh // vs.shape[1], axis=1)[..., None, None]
    t = jnp.maximum(table, 0)
    npl = table.shape[1]
    kl = kf[t].transpose(0, 2, 1, 3, 4).reshape(b, kvh, npl * PAGE, d)
    vl = vf[t].transpose(0, 2, 1, 3, 4).reshape(b, kvh, npl * PAGE, d)
    pos = jnp.arange(npl * PAGE)
    valid = pos[None, :] < lengths[:, None]
    if part is not None:
        inpart = (jnp.arange(npl)[None, :, None] == part[:, None, :]).any(-1)
        valid = valid & jnp.repeat(inpart, PAGE, axis=1)
    s = jnp.einsum("bkgd,bksd->bkgs", qm, kl,
                   precision=jax.lax.Precision.HIGHEST) * d ** -0.5
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", p, vl,
                     precision=jax.lax.Precision.HIGHEST)
    return out.reshape(b, h, d)


def run_case(case, seed, reps):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    q, layers, table, lengths, part, tokens = build(case, seed)

    def one(qi, k, v, ks, vs):
        return ops.aqua_paged_decode(qi, k, v, table, lengths, ks, vs, part,
                                     k_ratio=K_RATIO, block_dims=BD)

    @jax.jit
    def step(q, layers):
        return [one(q[i], *layers[i]) for i in range(LAYERS)]

    t0 = time.perf_counter()
    outs = step(q, layers)
    jax.block_until_ready(outs)
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(step(q, layers))
    t0 = time.perf_counter()
    for _ in range(reps):
        outs = step(q, layers)
    jax.block_until_ready(outs)
    ms = (time.perf_counter() - t0) * 1e3 / (reps * LAYERS)
    ref = reference(q[0], *layers[0], table, lengths, part)
    err = float(jnp.max(jnp.abs(outs[0].astype(jnp.float32) - ref)))
    return {"case": case, "tokens": tokens, "ms_per_call": ms,
            "max_abs_err": err, "finite": bool(np.isfinite(err)),
            "compile_s": compile_s}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--src", default=os.path.join(ROOT, "src"))
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cases", default=",".join(CASES))
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.src))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("paged_decode_bench: no TPU; times here would mean nothing",
              file=sys.stderr)
        return 2
    results = []
    for case in args.cases.split(","):
        r = run_case(case, args.seed, args.reps)
        print(json.dumps(r), flush=True)
        results.append(r)
    print(json.dumps({"src": args.src, "device": jax.devices()[0].device_kind,
                      "results": results}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
