"""README backend×mesh dispatch-matrix generator.

Renders the "Backends × mesh" support table in README.md straight from
:func:`repro.core.dispatch.resolve_dispatch_plan`, so the documented
matrix can never drift from what the engine actually dispatches: each
cell is a real resolved :class:`DispatchPlan` for that backend × cache
layout on a reference 2x2 data×model mesh (geometry that divides — the
README rows describe the *capability*, not a particular device count).
Resolution only reads mesh axis names/sizes, so an ``AbstractMesh``
suffices and no devices are required.

Regenerate the README block with::

    PYTHONPATH=src python -m repro.launch.matrix --readme README.md

``tests/test_dispatch_plan.py`` keeps the committed block golden against
this generator.
"""
from __future__ import annotations

import argparse
import dataclasses
import re

from jax.sharding import AbstractMesh

from repro.configs.base import (AquaConfig, AttentionConfig, CacheSpec,
                                QuantSpec, ServingConfig, SparsitySpec)
from repro.core.dispatch import resolve_dispatch_plan

BEGIN = "<!-- dispatch-matrix:begin (repro.launch.matrix — do not edit) -->"
END = "<!-- dispatch-matrix:end -->"

# Reference geometry: axis extents that divide (4 lanes over data=2,
# kv=2 over model=2, page_size a KERNEL_PAGE_MULTIPLE multiple).
_ATT = AttentionConfig(num_heads=4, num_kv_heads=2, head_dim=16)
_SERVING = ServingConfig(max_lanes=4, max_seq=64)

# (README row label, backend key, aqua config)
_ROWS = (
    ("dense-jnp", "dense-jnp", None),
    ("flash", "flash", None),
    ("aqua-masked-dense", "aqua-masked-dense",
     AquaConfig(k_ratio=0.75, block_dims=1)),
    ("aqua-block-sparse", "aqua-block-sparse",
     AquaConfig(k_ratio=0.5, block_dims=8)),
)


def _cell(plan) -> str:
    if plan.mesh_native:
        if plan.quantization != "none":
            return "shard_mapped Pallas kernel (scale-folded int8)"
        return "shard_mapped Pallas kernel"
    # the structured reasons are the REASON_* constants; the first one is
    # the highest-priority explanation in check order
    return f"shard_map/jnp reference ({plan.reasons[0]})"


def _chunk_cell(plan) -> str:
    if plan.chunked_prefill:
        return "interleaved (PREFILLING lanes)"
    return f"monolithic admit ({plan.chunked_reasons[0]})"


def _token_cell(plan) -> str:
    if plan.token_sparsity == "hierarchical":
        return "hierarchical (page-granular stage 1)"
    return f"none ({plan.token_reasons[0]})"


def generate_matrix() -> str:
    """The README table (markdown, BEGIN/END markers included)."""
    mesh = AbstractMesh((2, 2), ("data", "model"))
    lines = [
        BEGIN,
        "| backend | contiguous cache @ mesh | paged cache @ mesh "
        "| int8 paged cache @ mesh | chunked prefill @ budget "
        "| token sparsity @ keep 0.5 |",
        "|---|---|---|---|---|---|",
    ]
    layouts = (
        (CacheSpec(), QuantSpec()),
        (CacheSpec(page_size=8), QuantSpec()),
        (CacheSpec(page_size=8), QuantSpec(kv_dtype="int8")),
    )
    for label, backend, aqua in _ROWS:
        att = dataclasses.replace(_ATT, backend=backend)
        cells = []
        for cache, quant in layouts:
            serving = dataclasses.replace(_SERVING, cache=cache,
                                          quant=quant)
            plan = resolve_dispatch_plan(attention=att, aqua=aqua,
                                         serving=serving, mesh=mesh)
            cells.append(_cell(plan))
        # chunked-prefill admissibility is cache-layout independent; the
        # reference budget is one block-sparse q-chunk tile (128), the
        # geometry the REASON_CHUNK_GEOMETRY predicate requires
        serving = dataclasses.replace(_SERVING, prefill_budget_tokens=128)
        plan = resolve_dispatch_plan(attention=att, aqua=aqua,
                                     serving=serving, mesh=mesh)
        cells.append(_chunk_cell(plan))
        # stage-1 token sparsity needs a paged pool; every backend honors
        # it (a *selection* mode: kernel and reference paths stream/mask
        # the same participating-page set, so it is not a dispatch fork)
        serving = dataclasses.replace(
            _SERVING, cache=CacheSpec(page_size=8),
            sparsity=SparsitySpec(page_keep_ratio=0.5))
        plan = resolve_dispatch_plan(attention=att, aqua=aqua,
                                     serving=serving, mesh=mesh)
        cells.append(_token_cell(plan))
        lines.append(f"| `{label}` | {cells[0]} | {cells[1]} | {cells[2]} "
                     f"| {cells[3]} | {cells[4]} |")
    lines.append(END)
    return "\n".join(lines)


def embed(readme_text: str) -> str:
    """Replace the BEGIN..END block in ``readme_text`` with a freshly
    generated matrix (the markers must already exist)."""
    block = generate_matrix()
    pattern = re.compile(re.escape(BEGIN) + r".*?" + re.escape(END),
                         re.DOTALL)
    if not pattern.search(readme_text):
        raise ValueError("README has no dispatch-matrix markers")
    return pattern.sub(lambda _: block, readme_text)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--readme", default=None,
                    help="rewrite the marked block in this file in place "
                         "(default: print the table to stdout)")
    args = ap.parse_args(argv)
    if args.readme is None:
        print(generate_matrix())
        return
    with open(args.readme) as f:
        text = f.read()
    with open(args.readme, "w") as f:
        f.write(embed(text))
    print(f"[matrix] rewrote dispatch matrix in {args.readme}")


if __name__ == "__main__":
    main()
