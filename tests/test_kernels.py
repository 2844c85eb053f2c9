"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs the
pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aqua import chunk_topk_block_indices, topk_block_indices
from repro.kernels.ops import (aqua_decode, aqua_prefill, flash_attention,
                               round_k_dims, to_dim_major_blocks)
from repro.kernels.ref import (aqua_decode_ref, aqua_prefill_ref,
                               flash_attention_ref)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,s,d", [
    (1, 2, 2, 128, 32),
    (2, 4, 2, 256, 64),
    (2, 8, 2, 384, 64),   # GQA group 4, padded seq blocks
    (1, 4, 4, 256, 128),  # MHA
])
@pytest.mark.parametrize("k_ratio", [0.5, 0.75, 1.0])
def test_aqua_decode_matches_oracle(b, h, kv, s, d, dtype, k_ratio):
    ks = jax.random.split(jax.random.PRNGKey(42), 4)
    q = _rand(ks[0], (b, h, d), dtype)
    khat = _rand(ks[1], (b, kv, s, d), dtype)
    v = _rand(ks[2], (b, kv, s, d), dtype)
    lengths = jnp.full((b,), s, jnp.int32).at[0].set(max(1, s - 37))
    out = aqua_decode(q, khat, v, lengths, k_ratio=k_ratio, block_dims=8,
                      seq_blk=128)
    k_dims = min(d, max(8, int(round(k_ratio * d)) // 8 * 8))
    bi = topk_block_indices(q, k_dims, 8)
    ref = aqua_decode_ref(q, khat, v, bi, lengths, 8)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_aqua_decode_full_ratio_equals_exact_attention():
    """k_ratio=1.0 must reproduce exact softmax attention."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    b, h, kv, s, d = 1, 2, 1, 128, 32
    q = _rand(ks[0], (b, h, d), jnp.float32)
    khat = _rand(ks[1], (b, kv, s, d), jnp.float32)
    v = _rand(ks[2], (b, kv, s, d), jnp.float32)
    lengths = jnp.full((b,), s, jnp.int32)
    out = aqua_decode(q, khat, v, lengths, k_ratio=1.0, block_dims=8)
    qr = q.reshape(b, kv, h // kv, d)
    sc = jnp.einsum("bkgd,bksd->bkgs", qr, khat) / np.sqrt(d)
    w = jax.nn.softmax(sc, -1)
    ref = jnp.einsum("bkgs,bksd->bkgd", w, v).reshape(b, h, d)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_dim_major_blocks_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 64, 32))
    blk = to_dim_major_blocks(x, 8)
    assert blk.shape == (2, 3, 4, 8, 64)
    back = blk.reshape(2, 3, 32, 64).transpose(0, 1, 3, 2)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,s,d,window", [
    (1, 2, 2, 256, 32, None),
    (2, 4, 2, 256, 64, None),
    (1, 4, 1, 384, 64, 100),   # MQA + sliding window
    (1, 2, 2, 512, 128, 256),
])
def test_flash_attention_matches_oracle(b, h, kv, s, d, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(ks[0], (b, h, s, d), dtype)
    k = _rand(ks[1], (b, kv, s, d), dtype)
    v = _rand(ks[2], (b, kv, s, d), dtype)
    out = flash_attention(q, k, v, causal=True, window=window)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = _rand(ks[0], (1, 2, 128, 32), jnp.float32)
    k = _rand(ks[1], (1, 2, 128, 32), jnp.float32)
    v = _rand(ks[2], (1, 2, 128, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=False)
    ref = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# AQUA block-sparse chunked prefill
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,s,d,q_blk,k_blk,window", [
    (1, 2, 2, 64, 32, 16, 16, None),
    (2, 4, 2, 96, 32, 16, 32, None),    # GQA 2, ragged pad to chunk lcm
    (2, 8, 2, 128, 64, 32, 32, 24),     # GQA 4 + sliding window
    (1, 4, 4, 64, 64, 8, 16, None),     # MHA, small chunks
])
@pytest.mark.parametrize("k_ratio", [0.5, 0.75, 1.0])
def test_aqua_prefill_matches_oracle(b, h, kv, s, d, q_blk, k_blk, window,
                                     k_ratio, dtype):
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = _rand(ks[0], (b, h, s, d), dtype)
    khat = _rand(ks[1], (b, kv, s, d), dtype)
    v = _rand(ks[2], (b, kv, s, d), dtype)
    lengths = jnp.full((b,), s, jnp.int32).at[0].set(max(1, s - 13))
    out = aqua_prefill(q, khat, v, lengths, k_ratio=k_ratio, block_dims=8,
                       q_blk=q_blk, k_blk=k_blk, window=window)
    k_dims = round_k_dims(d, k_ratio, 8)
    bi = chunk_topk_block_indices(q, k_dims, 8, q_blk, lengths)
    ref = aqua_prefill_ref(q, khat, v, bi, lengths, 8, q_blk, window=window)
    sq = jnp.arange(s) < lengths[:, None]       # compare valid rows only
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(
        np.asarray(jnp.where(sq[:, None, :, None], out, 0), np.float32),
        np.asarray(jnp.where(sq[:, None, :, None], ref, 0), np.float32),
        rtol=tol, atol=tol)


def test_aqua_prefill_full_ratio_equals_flash():
    """k_ratio=1.0 streams every dim-block -> exact causal attention."""
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    b, h, kv, s, d = 1, 4, 2, 128, 32
    q = _rand(ks[0], (b, h, s, d), jnp.float32)
    k = _rand(ks[1], (b, kv, s, d), jnp.float32)
    v = _rand(ks[2], (b, kv, s, d), jnp.float32)
    out = aqua_prefill(q, k, v, None, k_ratio=1.0, block_dims=8,
                       q_blk=32, k_blk=32)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_aqua_prefill_chunk1_equals_per_query_selection():
    """q_blk=1 chunk selection must reduce to the paper's per-query top-k."""
    ks = jax.random.split(jax.random.PRNGKey(13), 1)[0]
    q = _rand(ks, (1, 2, 16, 32), jnp.float32)
    per_chunk = chunk_topk_block_indices(q, 16, 8, 1)
    per_query = topk_block_indices(q, 16, 8)
    np.testing.assert_array_equal(np.asarray(per_chunk),
                                  np.asarray(per_query))


# ---------------------------------------------------------------------------
# Paged decode: the whole-page body (all G query heads per grid step)
# against the masked-dense reference
# ---------------------------------------------------------------------------

PD, PPS_, PNP = 32, 8, 5              # head dim, page size, pages per lane
PAGED_LENGTHS = (1, 2 * PPS_, 2 * PPS_ + 5, PNP * PPS_)  # one token, a page
                                      # boundary, mid-page, full capacity


def _paged_setup(g, kvh=2, seed=3):
    """Four lanes at PAGED_LENGTHS over a shuffled pool; table entries
    past each lane's length are -1. Returns q, pools, table, lengths and
    the pages left free (no lane maps them)."""
    b = len(PAGED_LENGTHS)
    npool = b * PNP + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _rand(ks[0], (b, kvh * g, PD), jnp.float32)
    kp = _rand(ks[1], (npool, kvh, PPS_, PD), jnp.float32)
    vp = _rand(ks[2], (npool, kvh, PPS_, PD), jnp.float32)
    perm = np.random.RandomState(seed).permutation(npool)
    table = perm[:b * PNP].reshape(b, PNP).astype(np.int32)
    lengths = np.asarray(PAGED_LENGTHS, np.int32)
    used = -(-lengths // PPS_)
    table[np.arange(PNP)[None, :] >= used[:, None]] = -1
    return q, kp, vp, jnp.asarray(table), jnp.asarray(lengths), perm[b * PNP:]


def _masked_dense(q, kp, vp, table, lengths, k_ratio, part=None):
    """``_masked_dense_decode_core`` on q masked by ``_aqua_mask`` over each
    lane's gathered pages (positions of pages outside ``part`` invalid)."""
    from repro.configs.base import AquaConfig
    from repro.core.attention import _aqua_mask, _masked_dense_decode_core
    b, h, d = q.shape
    kvh = kp.shape[1]
    aqua = AquaConfig(k_ratio=k_ratio, block_dims=8)
    qq = (q * _aqua_mask(q, aqua, d)).reshape(b, kvh, h // kvh, d)
    t = jnp.maximum(table, 0)
    k = kp[t].transpose(0, 2, 1, 3, 4).reshape(b, kvh, -1, d)
    v = vp[t].transpose(0, 2, 1, 3, 4).reshape(b, kvh, -1, d)
    pos = jnp.broadcast_to(jnp.arange(k.shape[2], dtype=jnp.int32),
                           (b, k.shape[2]))
    if part is not None:
        inpart = (jnp.arange(PNP)[None, :, None]
                  == part[:, None, :]).any(-1)             # (B, NP)
        pos = jnp.where(jnp.repeat(inpart, PPS_, axis=1), pos, -1)
    out, _ = _masked_dense_decode_core(qq, k, v, pos, lengths, head_dim=d,
                                       window=None)
    return out.reshape(b, h, d)


def _whole_pages(q, kp, vp, table, lengths, k_ratio, pps, part=None,
                 ks=None, vs=None):
    """The whole-page body itself at ``pps`` pages per grid step; None
    goes through ``ops.aqua_paged_decode`` and its own step size."""
    from repro.configs.base import AquaConfig
    from repro.core.attention import _aqua_mask
    from repro.kernels.aqua_decode import aqua_paged_decode_attention
    from repro.kernels.ops import aqua_paged_decode, attended_pages
    if pps is None:
        return aqua_paged_decode(q, kp, vp, table, lengths, ks, vs, part,
                                 k_ratio=k_ratio, block_dims=8)
    d = q.shape[-1]
    qm = q * _aqua_mask(q, AquaConfig(k_ratio=k_ratio, block_dims=8), d)
    ids, n, tails = attended_pages(table, lengths, part, PPS_)
    return aqua_paged_decode_attention(qm, kp, vp, ids, n, tails, ks, vs,
                                       pages_per_step=pps, scale=d ** -0.5)


@pytest.mark.parametrize("pps", [None, 2, 3])   # 2, 3 do not divide 5 pages
@pytest.mark.parametrize("g", [1, 2, 4])
@pytest.mark.parametrize("k_ratio", [0.5, 0.75, 1.0])
def test_whole_page_paged_decode_matches_references(k_ratio, g, pps):
    q, kp, vp, table, lengths, _ = _paged_setup(g)
    out = _whole_pages(q, kp, vp, table, lengths, k_ratio, pps)
    ref = _masked_dense(q, kp, vp, table, lengths, k_ratio)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pps", [None, 2])
def test_whole_page_paged_decode_never_reads_past_length(pps):
    """Every table entry past a lane's length names a page of NaN K and V,
    and so do the slots past the length in each lane's last page: the
    output stays finite and equal to the reference on clean pages. (A
    body that reads such pages and masks only their scores turns NaN in
    p @ v.)"""
    q, kp, vp, table, lengths, free = _paged_setup(2)
    nan_page = int(free[0])
    poisoned = jnp.where(table < 0, nan_page, table)
    last = table[jnp.arange(table.shape[0]), (lengths - 1) // PPS_]
    slot = jnp.arange(PPS_)[None, :]
    tail = slot >= (lengths - (lengths - 1) // PPS_ * PPS_)[:, None]
    kp_nan = kp.at[last].set(jnp.where(tail[:, None, :, None], jnp.nan,
                                       kp[last]))
    vp_nan = vp.at[last].set(jnp.where(tail[:, None, :, None], jnp.nan,
                                       vp[last]))
    kp_nan = kp_nan.at[nan_page].set(jnp.nan)
    vp_nan = vp_nan.at[nan_page].set(jnp.nan)
    out = _whole_pages(q, kp_nan, vp_nan, poisoned, lengths, 0.75, pps)
    assert np.isfinite(np.asarray(out)).all()
    ref = _masked_dense(q, kp, vp, table, lengths, 0.75)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pps", [None, 2])
def test_whole_page_paged_decode_participating_pages(pps):
    """Hierarchical pages on the whole-page body: the participation table
    is composed into each lane's page list; entries past the length (the
    sorted table's tail) are neither read nor attended."""
    q, kp, vp, table, lengths, _ = _paged_setup(2)
    part = jnp.asarray([[0, 2, 4], [0, 1, 3], [1, 2, 4], [0, 3, 4]],
                       jnp.int32)
    out = _whole_pages(q, kp, vp, table, lengths, 0.5, pps, part=part)
    ref = _masked_dense(q, kp, vp, table, lengths, 0.5, part=part)
    # lane 0 holds one token on page 0: part's later pages are past it
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("heads_per_scale", [True, False])
def test_whole_page_paged_decode_int8_pools(heads_per_scale):
    """int8 pools on the whole-page body: per-page scales fold into the
    score scale and p, matching the reference on dequantized pools."""
    from repro.core.kvcache import dequant_pages
    q, kp, vp, table, lengths, _ = _paged_setup(2)
    sh = kp.shape[1] if heads_per_scale else 1
    axes = (2, 3) if sh > 1 else (1, 2, 3)
    ks = (jnp.abs(kp).max(axis=axes) / 127.0).reshape(-1, sh)
    vs = (jnp.abs(vp).max(axis=axes) / 127.0).reshape(-1, sh)
    kq = jnp.round(kp / ks[..., None, None]).astype(jnp.int8)
    vq = jnp.round(vp / vs[..., None, None]).astype(jnp.int8)
    out = _whole_pages(q, kq, vq, table, lengths, 0.75, 2, ks=ks, vs=vs)
    ref = _masked_dense(q, dequant_pages(kq, ks), dequant_pages(vq, vs),
                        table, lengths, 0.75)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
