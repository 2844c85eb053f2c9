"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler builds each kernel for a v5e:2x2
topology described in a fixture (never at import), at qwen3-0.6b's
published attention widths (16 query heads, 8 KV heads, head_dim 128,
``block_dims=8``, 12 of 16 dim-blocks kept). Mosaic's block-shape,
layout and VMEM rules reject here what interpret mode accepts. The
persistent compilation cache is off around the compiles: entries built
for a described device cannot be read back without one.

The compiled kernels keep the instruction names a profile's readers
match (each ``pallas_call`` pins its ``name``), and the AQUA stages
around them keep their ``jax.named_scope`` in the ops' metadata.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops

B, H, KV, D = 8, 16, 8, 128          # lanes and qwen3-0.6b attention widths
S, P, PS, KP = 4096, 320, 128, 8     # cache slots, pool pages, page size,
                                     # hierarchical kept pages
DECODE = dict(k_ratio=0.75, block_dims=8, interpret=False)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _paged(k_dtype, scale_heads=None, part=False, b=B, lane_pages=S // PS,
           heads=H):
    def fn(q, k, v, table, lengths, *extra):
        extra = list(extra)
        part_idx = extra.pop() if part else None
        ks, vs = extra if scale_heads else (None, None)
        return kops.aqua_paged_decode(q, k, v, table, lengths, ks, vs,
                                      part_idx, **DECODE)
    shapes = [((b, heads, D), jnp.bfloat16), ((P, KV, PS, D), k_dtype),
              ((P, KV, PS, D), k_dtype), ((b, lane_pages), jnp.int32),
              ((b,), jnp.int32)]
    if scale_heads:
        shapes += [((P, scale_heads), jnp.float32)] * 2
    if part:
        shapes.append(((b, KP), jnp.int32))
    return fn, shapes


CASES = {
    "decode_contiguous": (
        lambda q, k, v, lengths: kops.aqua_decode(q, k, v, lengths, **DECODE),
        [((B, H, D), jnp.bfloat16), ((B, KV, S, D), jnp.bfloat16),
         ((B, KV, S, D), jnp.bfloat16), ((B,), jnp.int32)]),
    "decode_paged_bf16": _paged(jnp.bfloat16),
    "decode_paged_int8_page_head": _paged(jnp.int8, scale_heads=KV),
    "decode_paged_int8_page": _paged(jnp.int8, scale_heads=1),
    "decode_paged_hierarchical": _paged(jnp.bfloat16, part=True),
    "decode_paged_hierarchical_int8": _paged(jnp.int8, scale_heads=KV,
                                             part=True),
    # the benchmark cells' lanes and pages per lane (long-decode-8k,
    # short-chat), each over a 320-page pool
    "decode_paged_bf16_4x80": _paged(jnp.bfloat16, b=4, lane_pages=80),
    "decode_paged_bf16_32x10": _paged(jnp.bfloat16, b=32, lane_pages=10),
    # one query head per KV head (G = 1), as qwen1.5-4b: whole pages too
    "decode_paged_bf16_g1": _paged(jnp.bfloat16, heads=KV),
    "prefill_2048": (
        lambda q, k, v, lengths: kops.aqua_prefill(
            q, k, v, lengths, k_ratio=0.75, block_dims=8, q_blk=128,
            k_blk=128, scale=D ** -0.5, interpret=False),
        [((1, H, 2048, D), jnp.bfloat16), ((1, KV, 2048, D), jnp.bfloat16),
         ((1, KV, 2048, D), jnp.bfloat16), ((1,), jnp.int32)]),
    "flash_512": (
        lambda q, k, v: kops.flash_attention(q, k, v, causal=True, q_blk=128,
                                             k_blk=128, interpret=False),
        [((1, H, 512, D), jnp.bfloat16), ((1, KV, 512, D), jnp.bfloat16),
         ((1, KV, 512, D), jnp.bfloat16)]),
}


# the kernel instruction each case compiles to, as a profile names it
KERNEL_NAMES = {"decode_contiguous": "aqua_decode_attention",
                "prefill_2048": "aqua_prefill_attention",
                "flash_512": "flash_attention"}
KERNEL_NAMES.update({k: "aqua_paged_decode_attention"
                     for k in CASES if k.startswith("decode_paged_")})
# the AQUA stages each case's wrapper scopes: the paged cases read whole
# seq-major pages, with no relayout
SCOPES = {k: {"aqua.select"} if k.startswith("decode_paged_")
          else {"aqua.select", "aqua.kv_layout"}
          for k in CASES if not k.startswith("flash")}
SCOPES["flash_512"] = set()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    kernels = re.findall(r"^\s*(?:ROOT )?(%[\w.-]+) = [^\n]*"
                         r'custom_call_target="tpu_custom_call"', text,
                         flags=re.M)
    assert [k.rsplit(".", 1)[0] for k in kernels] == [
        "%" + KERNEL_NAMES[name]]
    scopes = set(re.findall(r'op_name="[^"]*?\b((?:aqua|kv)\.[a-z_]+)',
                            text))
    assert scopes == SCOPES[name]


def test_decode_step_module_name():
    """The engine's decode step lowers to the module the benchmark's
    ``decode_step_ms`` matches (``jit__step_impl``)."""
    from repro.configs import reduced
    from repro.configs.base import CacheSpec, ServingConfig
    from repro.models import build_model
    from repro.serving import ContinuousBatchingEngine
    from repro.serving.engine import _init_lane_state
    cfg = dataclasses.replace(reduced("qwen3-0.6b"), remat=False, aqua=None)
    scfg = ServingConfig(max_lanes=2, max_seq=32,
                         cache=CacheSpec(page_size=8, num_pages=8))
    eng = ContinuousBatchingEngine(cfg, build_model(cfg).init(
        jax.random.PRNGKey(0)), None, serving=scfg, backend="dense-jnp")
    state = eng.model.init_decode_state(2, 32)
    lowered = eng._step.lower(eng.params, state, _init_lane_state(2),
                              eng.proj, jax.random.PRNGKey(0),
                              use_top_k=False)
    assert re.search(r"^module @jit__step_impl\b", lowered.as_text(),
                     flags=re.M)
