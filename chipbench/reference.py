"""The plain reference: AQUA calibration, and a float32 forward pass with
the same AQUA semantics as the served path.

Nothing here imports the program. The model itself (its weights from the
seed, in the layout the program's model takes, and its float32 forward
pass over every layer, in whatever order and pattern its layers come) is
the configuration's architecture module, ``chipbench/arch/<architecture>.py``;
the same weights are handed to the program and to this reference. What
every architecture shares is here: the products with their float8 control,
RMSNorm, RoPE, the AQUA selection and attention core, the unembedding and
the gaps, and the calibration of the AQUA projections (paper section 6.1:
per layer and KV head, the eigenvectors of the Gram matrix of the post-RoPE
queries of the group and the shared key, in descending order of variance),
from the reference's own activations on ``data/calibration.txt``.

AQUA attention: q-hat = q P and k-hat = k P per KV head, then attention on
q-hat masked to the selected dim-blocks against the full k-hat, scaled by
1/sqrt(head_dim), over the keys each query may see (causal unless the
module says otherwise), softmax, times V. The selection keeps the
``k_dims`` / ``block_dims`` blocks with the largest summed |q-hat|: per
128-query tile of the prompt, summed over the tile's prompt rows (the
prefill kernel's selection), and per query for every token decoded after
the prompt.

Every matrix product runs at ``Precision.HIGHEST``. ``quant="fp8"`` rounds
both operands of every product to float8 e4m3 (weights per tensor,
activations per row, each scaled to the format's range): the control.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.yardstick import round_k_dims

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def weights_key(seed: int) -> jax.Array:
    """A raw threefry key from any seed >= 0 (stream 0 of the seed)."""
    words = np.random.SeedSequence([seed, 0]).generate_state(2)
    return jnp.asarray(words, jnp.uint32)


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------


def _q8(x, axes):
    """Round to float8 e4m3 after scaling the max over ``axes`` to 448."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _dot(eq, act, w, quant, act_axes=None, w_axes=None):
    """einsum of an activation and a weight (or second activation).
    ``*_axes`` name the axes an fp8 scale is shared over (None: all)."""
    if quant == "fp8":
        act, w = _q8(act, act_axes), _q8(w, w_axes)
    return jnp.einsum(eq, act, w, precision=HI,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, positions, theta):
    """Half-split rotary embedding; x (T, ..., D), positions (T,)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _select_mask(qh, prompt_len, k_dims, bd, q_blk):
    """0/1 mask over the head dim of q-hat (T, KV, G, D): per-tile
    selection over prompt rows, per-query selection after the prompt."""
    t, kvh, g, d = qh.shape
    nb, kb = d // bd, k_dims // bd
    bmag = jnp.abs(qh).reshape(t, kvh, g, nb, bd).sum(-1)      # (T,KV,G,NB)
    rows = jnp.arange(t)
    in_prompt = rows < prompt_len
    tile = (bmag * in_prompt[:, None, None, None]).reshape(
        t // q_blk, q_blk, kvh, g, nb).sum(1)                    # (NT,KV,G,NB)
    agg = jnp.where(in_prompt[:, None, None, None],
                    jnp.repeat(tile, q_blk, axis=0), bmag)
    _, idx = jax.lax.top_k(agg, kb)
    bmask = jax.nn.one_hot(idx, nb, dtype=qh.dtype).sum(-2)
    return jnp.repeat(bmask, bd, axis=-1)


def causal(qpos, kpos):
    """Keys each query may see: (Q, K) bool, every key at or before it."""
    return kpos[None, :] <= qpos[:, None]


def aqua_attention(conf, quant, q, k, v, proj, positions, prompt_len,
                   q_chunk, sees=causal):
    """AQUA attention of one sequence: q (T, KV, G, D), k and v (T, KV, D)
    after RoPE, ``proj`` (KV, D, D); returns (T, KV, G, D). ``sees(qpos,
    kpos)`` gives the keys each query may see."""
    aq = conf["aqua"]
    t = q.shape[0]
    hd = conf["head_dim"]
    qh = _dot("tkgd,kde->tkge", q, proj, quant, (2, 3))
    kh = _dot("tkd,kde->tke", k, proj, quant, (1, 2))
    k_dims = round_k_dims(hd, aq["k_ratio"], aq["block_dims"])
    qq = qh * _select_mask(qh, prompt_len, k_dims, aq["block_dims"],
                           aq["prefill_q_blk"])
    scale = 1.0 / float(hd) ** 0.5

    def chunk(i):
        qc = jax.lax.dynamic_slice_in_dim(qq, i * q_chunk, q_chunk, 0)
        s = _dot("ckgd,tkd->ckgt", qc, kh, quant, (1, 2, 3), (0, 2)) * scale
        qpos = i * q_chunk + jnp.arange(q_chunk)
        s = jnp.where(sees(qpos, positions)[:, None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        return _dot("ckgt,tkd->ckgd", w, v, quant, (1, 2, 3), (0, 2))
    o = jax.lax.map(chunk, jnp.arange(t // q_chunk))
    return o.reshape(t, *o.shape[2:])


def unembed_table(conf: dict, params: dict):
    return params["embed" if conf["tie_word_embeddings"] else "unembed"][
        "table"]


@functools.partial(jax.jit, static_argnames=("conf_key", "quant", "q_chunk"))
def _hidden(params, proj, tokens, prompt_len, *, conf_key, quant, q_chunk):
    arch, conf = _CONFS[conf_key]
    return arch.hidden(conf, quant, params, proj, tokens, prompt_len,
                       q_chunk)


# jit needs hashable statics: (architecture module, configuration) pairs
# are registered by the configuration's name
_CONFS: dict = {}


def hidden(arch, conf: dict, params, proj, tokens: np.ndarray,
           prompt_len: int, quant: Optional[str] = None, q_chunk: int = 256):
    """Final normed hidden states (T, d) in float32 for one sequence
    through ``arch.hidden``; ``tokens`` is padded to a multiple of
    ``q_chunk`` and of the prefill tile. Positions past the real sequence
    are ignored by the caller."""
    _CONFS[conf["name"]] = (arch, conf)
    return _hidden(params, proj, jnp.asarray(tokens, jnp.int32),
                   jnp.int32(prompt_len), conf_key=conf["name"],
                   quant=quant, q_chunk=q_chunk)


@functools.partial(jax.jit, static_argnames=("quant",))
def _logits(table, h, *, quant=None):
    return _dot("wd,vd->wv", h, table.astype(jnp.float32), quant, -1)


@jax.jit
def _gap_of(logits, toks):
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, toks[:, None], axis=-1)[:, 0]
    top2 = jax.lax.top_k(logits, 2)[0]
    return best - got, top2[:, 0] - top2[:, 1]


def _padded(x: np.ndarray, block: int = 128) -> jax.Array:
    """``x`` repeated at its last entry to a multiple of ``block``, so that
    every request's logits compile to one of a few shapes."""
    n = len(x)
    return jnp.asarray(np.pad(x, (0, -n % block), mode="edge"))


def served_gaps(table, h, positions: np.ndarray, toks: np.ndarray):
    """Reference best logit minus the reference logit of each served
    token, at the positions whose logits chose them; and the reference's
    best minus its second-best logit there (its margin)."""
    lg = _logits(table, h[_padded(positions)])
    gap, margin = _gap_of(lg, _padded(np.asarray(toks, np.int32)))
    n = len(positions)
    return np.asarray(gap)[:n], np.asarray(margin)[:n]


def control_choice(table, h_ctrl, positions: np.ndarray, quant: str):
    """Tokens the control puts first at each position."""
    lg = _logits(table, h_ctrl[_padded(positions)], quant=quant)
    return np.asarray(jnp.argmax(lg, axis=-1)).astype(np.int32)[
        :len(positions)]


# ---------------------------------------------------------------------------
# Calibration
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("conf_key",))
def _grams(params, tokens, *, conf_key):
    arch, conf = _CONFS[conf_key]
    # (L, B, T, KV, G, D) and (L, B, T, KV, D)
    qs, ks = arch.capture(conf, params, tokens)
    n, b, t, kvh, g, d = qs.shape
    qm = qs.transpose(0, 3, 1, 2, 4, 5).reshape(n, kvh, b * t * g, d)
    km = ks.transpose(0, 3, 1, 2, 4).reshape(n, kvh, b * t, d)
    dm = jnp.concatenate([qm, km], axis=2)
    return jnp.einsum("lkmd,lkme->lkde", dm, dm, precision=HI)


def corpus_tokens(path: str, vocab: int, rows: int, seq: int) -> np.ndarray:
    """Byte-level token windows of the calibration text: ``rows`` windows
    of ``seq`` bytes at evenly spaced offsets."""
    with open(path, "rb") as f:
        ids = np.frombuffer(f.read(), dtype=np.uint8).astype(np.int64)
    starts = np.linspace(0, len(ids) - seq, rows).astype(np.int64)
    return np.stack([ids[s:s + seq] % vocab for s in starts]).astype(np.int32)


def calibrate(arch, conf: dict, params, tokens: np.ndarray) -> jax.Array:
    """Projections (L, KV, D, D) float32, columns in descending variance,
    from ``arch.capture``."""
    _CONFS[conf["name"]] = (arch, conf)
    grams = np.asarray(_grams(params, jnp.asarray(tokens),
                              conf_key=conf["name"]), np.float64)
    _, vecs = np.linalg.eigh(grams)
    return jnp.asarray(vecs[..., ::-1].astype(np.float32))
