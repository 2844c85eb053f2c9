"""Causal flash-attention Pallas TPU kernel (prefill path).

Standard memory-efficient attention with online softmax; supports GQA
(kv head = query head // group) and sliding windows. Used by the prefill
benchmarks; AQUA prefill masking happens on the query side *before* this
kernel (masked-q identity, DESIGN.md §2), so the same kernel serves both.

Grid: (B, H, num_q_blocks, num_k_blocks), k innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, q_blk: int, k_blk: int, nkb: int,
            causal: bool, window):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)      # (q_blk, D)
    k = k_ref[0, 0].astype(jnp.float32)      # (k_blk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qb * q_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, k_blk), 0)
    kpos = kb * k_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, k_blk), 1)
    mask = jnp.ones((q_blk, k_blk), jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                       # (q_blk, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    v_blk = v_ref[0, 0].astype(jnp.float32)   # (k_blk, D)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kb == nkb - 1)
    def _write():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30))[None, None].astype(
                          o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_blk",
                                             "k_blk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window=None, q_blk: int = 128,
                    k_blk: int = 128, interpret=None) -> jax.Array:
    """q: (B, H, S, D); k, v: (B, KV, S, D). Returns (B, H, S, D).

    ``interpret=None`` resolves via runtime_flags: compiled on TPU,
    interpreted elsewhere.
    """
    from repro import runtime_flags as _rtf
    interpret = _rtf.resolve_interpret(interpret)
    b, h, s, d = q.shape
    kvh = k.shape[1]
    g = h // kvh
    assert s % q_blk == 0 and s % k_blk == 0, (s, q_blk, k_blk)
    nqb, nkb = s // q_blk, s // k_blk
    scale = 1.0 / (d ** 0.5)
    grid = (b, h, nqb, nkb)

    kernel = functools.partial(_kernel, scale=scale, q_blk=q_blk, k_blk=k_blk,
                               nkb=nkb, causal=causal, window=window)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_blk, d), lambda bi, hi, qb, kb: (bi, hi, qb, 0)),
            pl.BlockSpec((1, 1, k_blk, d),
                         lambda bi, hi, qb, kb, g=g: (bi, hi // g, kb, 0)),
            pl.BlockSpec((1, 1, k_blk, d),
                         lambda bi, hi, qb, kb, g=g: (bi, hi // g, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_blk, d),
                               lambda bi, hi, qb, kb: (bi, hi, qb, 0)),
        scratch_shapes=[
            pltpu.VMEM((q_blk, 1), jnp.float32),
            pltpu.VMEM((q_blk, 1), jnp.float32),
            pltpu.VMEM((q_blk, d), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), v.dtype),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
