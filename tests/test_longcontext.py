"""Long-context hierarchical AQUA: needle retrieval through the
two-stage (page-granular × dim-block) pipeline.

Ranking level at the true 32k geometry (256 pages of 128): a needle page
deep in the context whose H2O mass dominates must rank into a 32-page
keep set, while zeroed statistics degrade deterministically to
attention-sink + pinned recent tail and drop it.

Kernel level at a reduced long geometry: the hierarchical Pallas decode
kernel retrieves the needle's value when its page participates, misses it
when stage 1 drops the page, and a full participation table is
bit-identical to the plain paged kernel (`page_keep_ratio=1.0` is the
identity, not an approximation). The prefill analogue checks an identity
q-tile participation table against the monolithic kernel.

Engine level: `SparsitySpec(page_keep_ratio=1.0)` resolves to no token
sparsity at all — same plan, same tokens as an engine without the spec.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced
from repro.configs.base import (AquaConfig, CacheSpec, ServingConfig,
                                SparsitySpec)
from repro.core import selection
from repro.core.calibration import identity_projections
from repro.models import build_model
from repro.serving import ContinuousBatchingEngine, Request

PS = 128


def _paged_pools(khat, v):
    """Contiguous (B=1, KV, S, D) -> identity-table page pools."""
    kvh, s, d = khat.shape[1:]
    npg = s // PS
    pool_k = khat[0].reshape(kvh, npg, PS, d).transpose(1, 0, 2, 3)
    pool_v = v[0].reshape(kvh, npg, PS, d).transpose(1, 0, 2, 3)
    table = jnp.arange(npg, dtype=jnp.int32)[None]
    return pool_k, pool_v, table


# ---------------------------------------------------------------------------
# Ranking level: 32k context, 256 pages
# ---------------------------------------------------------------------------


def test_needle_page_ranks_in_at_32k():
    s, kvh = 32768, 2
    npl = s // PS
    kept = SparsitySpec(page_keep_ratio=0.125).kept_pages(npl)
    assert kept == 32
    acc = jnp.zeros((npl, kvh, PS), jnp.float32).at[77].set(1.0)
    table = jnp.arange(npl, dtype=jnp.int32)[None]
    count = jnp.full((1,), s, jnp.int32)
    part = np.asarray(selection.participating_pages(
        acc, table, count, page_size=PS, kept_pages=kept,
        pin_recent_pages=2))[0]
    assert 77 in part, part
    assert npl - 1 in part and npl - 2 in part          # recency pin
    assert (np.sort(part) == part).all()
    # the numpy --verify oracle agrees at this geometry
    ref = selection.reference_participating_pages(
        acc, table, count, page_size=PS, kept_pages=kept,
        pin_recent_pages=2)
    np.testing.assert_array_equal(part, ref[0])


def test_zero_stats_degrade_to_sink_plus_pinned_tail():
    """A cache with no H2O mass (hierarchical serving keeps h2o off) must
    rank deterministically: earliest pages (attention sink, lowest-index
    tie-break) plus the pinned recent pages — never arbitrary."""
    s, kvh = 32768, 2
    npl = s // PS
    acc = jnp.zeros((npl, kvh, PS), jnp.float32)
    table = jnp.arange(npl, dtype=jnp.int32)[None]
    count = jnp.full((1,), s, jnp.int32)
    part = np.asarray(selection.participating_pages(
        acc, table, count, page_size=PS, kept_pages=32,
        pin_recent_pages=2))[0]
    expect = np.sort(np.concatenate([np.arange(30), [npl - 2, npl - 1]]))
    np.testing.assert_array_equal(part, expect)


# ---------------------------------------------------------------------------
# Kernel level: reduced long geometry (1024 tokens, 8 pages)
# ---------------------------------------------------------------------------


def _needle_setup():
    b, h, kvh, s, d = 1, 4, 2, 1024, 64
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    # one query direction shared by every head, so a single needle key
    # dominates all of them (logit ~ 3·|q|²/√d ≫ background)
    qvec = jax.random.normal(ks[0], (d,))
    q = jnp.broadcast_to(qvec, (b, h, d))
    khat = jax.random.normal(ks[1], (b, kvh, s, d))
    v = jax.random.normal(ks[2], (b, kvh, s, d))
    # plant the needle mid-context (page 3) with a recognizable value
    needle = 3 * PS + 5
    khat = khat.at[0, :, needle].set(3.0 * qvec)
    v = v.at[0, :, needle, :].set(5.0)
    lengths = jnp.full((b,), s, jnp.int32)
    return q, khat, v, lengths


def test_hier_kernel_retrieves_needle_when_mass_ranks_it_in():
    from repro.kernels.ops import aqua_paged_decode
    q, khat, v, lengths = _needle_setup()
    pool_k, pool_v, table = _paged_pools(khat, v)
    npl = pool_k.shape[0]
    acc = jnp.zeros((npl, 2, PS), jnp.float32).at[3].set(1.0)
    part = selection.participating_pages(
        acc, table, lengths, page_size=PS, kept_pages=4,
        pin_recent_pages=2)
    assert 3 in np.asarray(part)[0]
    out = aqua_paged_decode(q, pool_k, pool_v, table, lengths,
                            part_idx=part, k_ratio=1.0, block_dims=8)
    # softmax is dominated by the needle -> output pulled to its value
    assert float(jnp.max(jnp.abs(out - 5.0))) < 0.5, out


def test_hier_kernel_misses_needle_when_page_dropped():
    from repro.kernels.ops import aqua_paged_decode
    q, khat, v, lengths = _needle_setup()
    pool_k, pool_v, table = _paged_pools(khat, v)
    npl = pool_k.shape[0]
    acc = jnp.zeros((npl, 2, PS), jnp.float32)          # no mass anywhere
    part = selection.participating_pages(
        acc, table, lengths, page_size=PS, kept_pages=4,
        pin_recent_pages=2)
    assert 3 not in np.asarray(part)[0]                 # sink + tail only
    out = aqua_paged_decode(q, pool_k, pool_v, table, lengths,
                            part_idx=part, k_ratio=1.0, block_dims=8)
    # the needle's value never streams: output stays near the background
    assert float(jnp.max(jnp.abs(out - 5.0))) > 2.0, out


def test_full_participation_bit_identical_to_paged_kernel():
    from repro.kernels.ops import aqua_paged_decode
    q, khat, v, lengths = _needle_setup()
    pool_k, pool_v, table = _paged_pools(khat, v)
    npl = pool_k.shape[0]
    ident = jnp.arange(npl, dtype=jnp.int32)[None]
    for kr in (0.5, 1.0):
        out_h = aqua_paged_decode(q, pool_k, pool_v, table, lengths,
                                  part_idx=ident, k_ratio=kr,
                                  block_dims=8)
        out_p = aqua_paged_decode(q, pool_k, pool_v, table, lengths,
                                  k_ratio=kr, block_dims=8)
        np.testing.assert_array_equal(np.asarray(out_h), np.asarray(out_p))


def test_prefill_identity_tile_table_bit_identical():
    """An identity q-tile participation table walks the same tiles in the
    same order as the monolithic prefill kernel — bit-identical."""
    from repro.core.aqua import chunk_topk_block_indices
    from repro.kernels.aqua_prefill import aqua_prefill_attention
    from repro.kernels.ops import aqua_prefill, round_k_dims, \
        to_dim_major_blocks
    b, h, kvh, s, d = 1, 2, 2, 512, 32
    q_blk = k_blk = 128
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, h, s, d))
    khat = jax.random.normal(ks[1], (b, kvh, s, d))
    v = jax.random.normal(ks[2], (b, kvh, s, d))
    lengths = jnp.full((b,), s, jnp.int32)
    ref = aqua_prefill(q, khat, v, lengths, k_ratio=0.5, block_dims=8,
                       q_blk=q_blk, k_blk=k_blk)

    nqc, nkc = s // q_blk, s // k_blk
    nb = d // 8
    k_dims = round_k_dims(d, 0.5, 8)
    block_idx = chunk_topk_block_indices(q, k_dims, 8, q_blk, lengths)
    qb = q.reshape(b, h, nqc, q_blk, nb, 8).transpose(0, 1, 2, 4, 3, 5)
    q_sel = jnp.take_along_axis(qb, block_idx[..., None, None], axis=3)
    kc_part = jnp.broadcast_to(jnp.arange(nkc, dtype=jnp.int32),
                               (b, nqc, nkc))
    out = aqua_prefill_attention(q_sel, to_dim_major_blocks(khat, 8), v,
                                 block_idx, lengths, kc_part,
                                 block_dims=8, q_blk=q_blk, k_blk=k_blk,
                                 causal=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# Engine level: page_keep_ratio=1.0 is the identity configuration
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def block_sparse_model():
    cfg = dataclasses.replace(reduced("qwen3-0.6b"), remat=False,
                              dtype="float32",
                              aqua=AquaConfig(k_ratio=0.5, block_dims=8))
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    proj = identity_projections(cfg.num_layers, cfg.attention.num_kv_heads,
                                cfg.attention.head_dim)
    return cfg, params, proj


def _trace(cfg, n=4, max_new=6):
    rng = np.random.default_rng(3)
    return [Request(uid=i,
                    tokens=rng.integers(0, cfg.vocab_size, size=(12,),
                                        dtype=np.int32),
                    max_new_tokens=max_new, arrival=float(i))
            for i in range(n)]


def test_keep_ratio_one_is_engine_identity(block_sparse_model):
    cfg, params, proj = block_sparse_model
    scfg = ServingConfig(max_lanes=2, max_seq=32, max_new_tokens=6,
                         prompt_bucket=8,
                         cache=CacheSpec(page_size=8, num_pages=10))
    reqs = _trace(cfg)
    base = ContinuousBatchingEngine(cfg, params, proj, serving=scfg,
                                    backend="aqua-block-sparse").run(reqs)
    full = dataclasses.replace(scfg,
                               sparsity=SparsitySpec(page_keep_ratio=1.0))
    eng = ContinuousBatchingEngine(cfg, params, proj, serving=full,
                                   backend="aqua-block-sparse")
    assert eng.dispatch_plan().token_sparsity == "none"
    assert eng.kept_pages is None
    out = eng.run(reqs)
    for uid in base:
        assert list(base[uid].tokens) == list(out[uid].tokens), uid


def test_hierarchical_engine_serves_and_drops_pages(block_sparse_model):
    """A ratio below 1.0 on a paged engine plans hierarchical token
    sparsity, resolves a kept-page budget below the lane page count, and
    still serves every request to completion."""
    cfg, params, proj = block_sparse_model
    scfg = ServingConfig(max_lanes=2, max_seq=64, max_new_tokens=8,
                         prompt_bucket=8,
                         cache=CacheSpec(page_size=8, num_pages=18),
                         sparsity=SparsitySpec(page_keep_ratio=0.5))
    eng = ContinuousBatchingEngine(cfg, params, proj, serving=scfg,
                                   backend="aqua-block-sparse")
    plan = eng.dispatch_plan()
    assert plan.token_sparsity == "hierarchical", plan
    assert eng.kept_pages == 4                           # 0.5 × 8 pages
    rng = np.random.default_rng(9)
    reqs = [Request(uid=i,
                    tokens=rng.integers(0, cfg.vocab_size, size=(30,),
                                        dtype=np.int32),
                    max_new_tokens=8, arrival=float(i)) for i in range(3)]
    out = eng.run(reqs)
    assert all(len(o.tokens) == 8 for o in out.values())


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
