"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (us_per_call is CPU wall time of
the jitted callable where meaningful, 0.0 for pure-metric rows; derived
carries the paper metric). Roofline terms come from the dry-run artifacts
via benchmarks.roofline, not from CPU timing.

``--fast`` runs the CI-smoke subset: the trained-model-free benches plus
the quality sweeps (which reuse one cached trained model, see
benchmarks.common); ``--json PATH`` additionally writes the rows as a
JSON list of
``{"name", "us_per_call", "derived"}`` objects (uploaded as a CI
artifact).

``--baseline`` refreshes the committed bench-trajectory baseline: it
implies ``--fast`` and writes the canonical ``BENCH_serving.json`` at the
repo root (run under XLA_FLAGS=--xla_force_host_platform_device_count=8
so the mesh-serving rows — including the shard_mapped AQUA block-sparse
kernel rows ``serving/aqua-*@mesh2x2`` and
``prefill/aqua_block_sparse@mesh2x2`` — are measured rather than emitted
as skipped sentinels, then commit the diff; CI's ``benchmarks/compare.py``
gate judges every PR against it).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

# self-sufficient when invoked as `python benchmarks/run.py`
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--fast", action="store_true", help="trained-model-free subset (CI smoke)"
    )
    ap.add_argument(
        "--json", default=None, metavar="PATH", help="also write rows as JSON to PATH"
    )
    ap.add_argument(
        "--baseline",
        action="store_true",
        help="refresh the committed BENCH_serving.json (implies --fast)",
    )
    args = ap.parse_args(argv)
    if args.baseline:
        args.fast = True
        args.json = os.path.join(_ROOT, "BENCH_serving.json")

    from benchmarks import fidelity, quality
    fast_benches = [
        fidelity.breakeven,
        fidelity.prefill_backends,
        fidelity.kernel_bandwidth,
        fidelity.quant_fidelity,
        fidelity.serving_throughput,
        fidelity.longcontext_bench,
        quality.quality_sweep,
        quality.hf_ingest_quality,
    ]
    full_benches = [
        fidelity.fig2_info_retention,
        fidelity.table1_standalone,
        fidelity.table2_aqua_h2o,
        fidelity.table3_aqua_memory,
        *fast_benches,
        fidelity.block_granularity,
    ]
    benches = fast_benches if args.fast else full_benches

    print("name,us_per_call,derived")
    rows = []
    failures = 0
    for bench in benches:
        try:
            for name, us, derived in bench():
                print(f"{name},{us:.1f},{derived}")
                rows.append({"name": name, "us_per_call": us, "derived": derived})
        except Exception:
            failures += 1
            print(f"{bench.__name__},ERROR,", file=sys.stderr)
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro import runtime_flags

    runtime_flags.configure_compile_cache()
    main()
