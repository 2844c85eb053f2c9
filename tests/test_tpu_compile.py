"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler builds each kernel for a v5e:2x2
topology described in a fixture (never at import), at qwen3-0.6b's
published attention widths (16 query heads, 8 KV heads, head_dim 128,
``block_dims=8``, 12 of 16 dim-blocks kept). Mosaic's block-shape,
layout and VMEM rules reject here what interpret mode accepts. The
persistent compilation cache is off around the compiles: entries built
for a described device cannot be read back without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops

B, H, KV, D = 8, 16, 8, 128          # lanes and qwen3-0.6b attention widths
S, P, PS, KP = 4096, 256, 128, 8     # cache slots, pool pages, page size,
                                     # hierarchical kept pages
DECODE = dict(k_ratio=0.75, block_dims=8, seq_blk=128, interpret=False)


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


def _paged(k_dtype, scale_heads=None, part=False):
    def fn(q, k, v, table, lengths, *extra):
        extra = list(extra)
        part_idx = extra.pop() if part else None
        ks, vs = extra if scale_heads else (None, None)
        return kops.aqua_paged_decode(q, k, v, table, lengths, ks, vs,
                                      part_idx, **DECODE)
    shapes = [((B, H, D), jnp.bfloat16), ((P, KV, PS, D), k_dtype),
              ((P, KV, PS, D), k_dtype), ((B, S // PS), jnp.int32),
              ((B,), jnp.int32)]
    if scale_heads:
        shapes += [((P, scale_heads), jnp.float32)] * 2
    if part:
        shapes.append(((B, KP), jnp.int32))
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


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
