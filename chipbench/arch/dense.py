"""The dense block: attention, then a gated SiLU MLP (the qwen2 and qwen3
model types, and the program's ``family="dense"``).

Per layer: RMSNorm, q/k/v projections (with bias where the configuration
has it), per-head q/k RMSNorm where it has that, half-split RoPE, AQUA
attention (``reference.aqua_attention``), output projection; RMSNorm and a
gated SiLU MLP; every layer alike. Everything that is specific to this
model lives here, its forward pass over the layers included; the
benchmark reaches it through ``program_config``, ``init_params``,
``hidden``, ``capture`` and ``shapes``, as it reaches any module of
``chipbench/arch/`` that a configuration's ``architecture`` names.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import (HI, NEG_INF, _dot, _rms, _rope,
                                 aqua_attention)
from chipbench.yardstick import Shapes, round_k_dims

# random weights: the std of q/k/v biases
BIAS_STD = 0.5


def program_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file (published
    key names, plus the serving and AQUA settings it states)."""
    from repro.configs.base import AquaConfig, AttentionConfig, ModelConfig
    serve, aqua = conf["serve"], conf["aqua"]
    attention = AttentionConfig(
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"], qk_norm=serve["qk_norm"],
        qkv_bias=serve["qkv_bias"], rope_theta=float(conf["rope_theta"]),
        backend=serve["backend"])
    return ModelConfig(
        name=conf["name"], family="dense",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        attention=attention, norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        act=conf["hidden_act"], dtype=serve["dtype"],
        param_dtype=serve["param_dtype"], remat=False,
        aqua=AquaConfig(k_ratio=aqua["k_ratio"],
                        block_dims=aqua["block_dims"],
                        prefill_q_blk=aqua["prefill_q_blk"],
                        prefill_k_blk=aqua["prefill_k_blk"],
                        decode_seq_blk=aqua["decode_seq_blk"]))


def init_params(conf: dict, key: jax.Array) -> dict:
    """Random weights in the program's dense-model layout, in the
    configuration's parameter dtype. Weights are N(0, 1) over the square
    root of their fan-in, ``wq`` and ``wk`` times the configuration's
    ``init.qk_gain`` (1 where it states none); biases N(0, ``BIAS_STD``);
    norm scales 1."""
    d, f, v = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    n = conf["num_hidden_layers"]
    h, kvh, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    g = h // kvh
    serve = conf["serve"]
    gain = conf.get("init", {}).get("qk_gain", 1.0)
    dt = jnp.dtype(serve["param_dtype"])
    keys = iter(jax.random.split(key, 16))

    def normal(shape, fan_in=None, std=None):
        std = fan_in ** -0.5 if std is None else std
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dt)

    ones = lambda *s: jnp.ones(s, dt)
    attn = {"wq": normal((n, d, kvh, g, hd), std=gain * d ** -0.5),
            "wk": normal((n, d, kvh, hd), std=gain * d ** -0.5),
            "wv": normal((n, d, kvh, hd), d),
            "wo": normal((n, kvh, g, hd, d), h * hd)}
    if serve["qkv_bias"]:
        attn["bq"] = normal((n, kvh, g, hd), std=BIAS_STD)
        attn["bk"] = normal((n, kvh, hd), std=BIAS_STD)
        attn["bv"] = normal((n, kvh, hd), std=BIAS_STD)
    if serve["qk_norm"]:
        attn["q_norm"] = ones(n, hd)
        attn["k_norm"] = ones(n, hd)
    params = {
        "embed": {"table": normal((v, d), d)},
        "layers": {"ln1": ones(n, d), "ln2": ones(n, d), "attn": attn,
                   "ffn": {"w1": normal((n, d, f), d),
                           "w2": normal((n, f, d), f),
                           "w3": normal((n, d, f), d)}},
        "ln_f": ones(d),
    }
    if not conf["tie_word_embeddings"]:
        params["unembed"] = {"table": normal((v, d), d)}
    return params


def hidden(conf, quant, params, proj, tokens, prompt_len, q_chunk):
    """Final normed hidden states (T, d) in float32 of one sequence of
    ``tokens``: every layer with its AQUA projection ``proj[i]``."""
    t = tokens.shape[0]
    positions = jnp.arange(t, dtype=jnp.int32)
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def body(xc, lp):
        weights, pr = lp
        return _layer(conf, quant, xc, weights, pr, positions, prompt_len,
                      q_chunk), None
    x, _ = jax.lax.scan(body, x, (params["layers"], proj))
    return _rms(x, params["ln_f"].astype(jnp.float32),
                float(conf["rms_norm_eps"]))


def capture(conf, params, tokens):
    """The calibration pass over a batch of ``tokens`` (B, T): post-RoPE q
    (L,B,T,KV,G,D) and k (L,B,T,KV,D) of every layer, float32, plain
    attention (no AQUA)."""
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    _, (qs, ks) = jax.lax.scan(
        lambda x, weights: _capture_layer(conf, x, weights, positions), x,
        params["layers"])
    return qs, ks


def _layer(conf, quant, x, weights, proj, positions, prompt_len, q_chunk):
    """One layer of the float32 reference over one sequence x (T, d), with
    that layer's ``weights`` and AQUA projection ``proj``."""
    a = conf["serve"]
    eps = float(conf["rms_norm_eps"])
    p = jax.tree.map(lambda w: w.astype(jnp.float32), weights)
    at = p["attn"]
    h = _rms(x, p["ln1"], eps)
    q = _dot("tm,mkgd->tkgd", h, at["wq"], quant, -1)
    k = _dot("tm,mkd->tkd", h, at["wk"], quant, -1)
    v = _dot("tm,mkd->tkd", h, at["wv"], quant, -1)
    if a["qkv_bias"]:
        q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
    if a["qk_norm"]:
        q = _rms(q, at["q_norm"], eps)
        k = _rms(k, at["k_norm"], eps)
    theta = float(conf["rope_theta"])
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    o = aqua_attention(conf, quant, q, k, v, proj, positions, prompt_len,
                       q_chunk)
    x = x + _dot("tkgd,kgdm->tm", o, at["wo"], quant, (1, 2, 3))
    h = _rms(x, p["ln2"], eps)
    ffn = p["ffn"]
    up = jax.nn.silu(_dot("tm,mf->tf", h, ffn["w1"], quant, -1)) \
        * _dot("tm,mf->tf", h, ffn["w3"], quant, -1)
    return x + _dot("tf,fm->tm", up, ffn["w2"], quant, -1)


def _capture_layer(conf, x, weights, positions):
    """One layer of the calibration pass over a batch x (B, T, d): plain
    attention (no AQUA); returns x and the layer's post-RoPE q
    (B,T,KV,G,D) and k (B,T,KV,D), float32."""
    a = conf["serve"]
    eps = float(conf["rms_norm_eps"])
    theta = float(conf["rope_theta"])
    hd = conf["head_dim"]
    p = jax.tree.map(lambda w: w.astype(jnp.float32), weights)
    at = p["attn"]
    h = _rms(x, p["ln1"], eps)
    q = jnp.einsum("btm,mkgd->btkgd", h, at["wq"], precision=HI)
    k = jnp.einsum("btm,mkd->btkd", h, at["wk"], precision=HI)
    v = jnp.einsum("btm,mkd->btkd", h, at["wv"], precision=HI)
    if a["qkv_bias"]:
        q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
    if a["qk_norm"]:
        q = _rms(q, at["q_norm"], eps)
        k = _rms(k, at["k_norm"], eps)
    rope = jax.vmap(lambda z: _rope(z, positions, theta))
    q, k = rope(q), rope(k)
    s = jnp.einsum("bskgd,btkd->bkgst", q, k, precision=HI) / hd ** 0.5
    s = jnp.where(positions[None, :] <= positions[:, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", w, v, precision=HI)
    x = x + jnp.einsum("bskgd,kgdm->bsm", o, at["wo"], precision=HI)
    h = _rms(x, p["ln2"], eps)
    ffn = p["ffn"]
    up = jax.nn.silu(jnp.einsum("btm,mf->btf", h, ffn["w1"],
                                precision=HI)) \
        * jnp.einsum("btm,mf->btf", h, ffn["w3"], precision=HI)
    x = x + jnp.einsum("btf,fm->btm", up, ffn["w2"], precision=HI)
    return x, (q, k)


def active_params(conf: dict) -> int:
    """Per-token parameters: attention and gated MLP of every layer plus
    the unembedding (embeddings excluded)."""
    d, hd = conf["hidden_size"], conf["head_dim"]
    h, kvh = conf["num_attention_heads"], conf["num_key_value_heads"]
    attn = d * hd * (h + 2 * kvh) + h * hd * d
    mlp = 3 * d * conf["intermediate_size"]
    return conf["num_hidden_layers"] * (attn + mlp) + d * conf["vocab_size"]


def shapes(conf: dict) -> Shapes:
    """The sizes behind ``step_mfu`` and the AQUA rooflines: every layer
    attends its whole context."""
    d = conf["head_dim"]
    return Shapes(active=active_params(conf),
                  unembed=conf["hidden_size"] * conf["vocab_size"],
                  windows=(None,) * conf["num_hidden_layers"],
                  heads=conf["num_attention_heads"],
                  kv_heads=conf["num_key_value_heads"], head_dim=d,
                  k_dims=round_k_dims(d, conf["aqua"]["k_ratio"],
                                      conf["aqua"]["block_dims"]))
