"""A block shape the benchmark has never run is files only: a test writes
a toy mixture-of-experts architecture module, its configuration and its
cell into a fresh tree, and the harness as it stands runs the cell end to
end on the CPU, with the module's own weights, reference block and counts.
"""
import textwrap

from chipbench import run, spec
from chipbench.tests import tiny

# Two layers of the dense block's attention, then a routed gated-SiLU MLP:
# a float32 router, softmax, the top ``num_experts_per_tok`` experts with
# their weights renormalised, on the program's ``family="moe"``. The
# module runs its own layers, one by one with the layer's index, as a
# module whose layers differ (windowed and full, recurrent and attention)
# would.
TOY_MOE = textwrap.dedent('''
    import jax
    import jax.numpy as jnp

    from chipbench import reference as R
    from chipbench.yardstick import Shapes, round_k_dims


    def program_config(conf):
        from repro.configs.base import (AquaConfig, AttentionConfig,
                                        ModelConfig, MoEConfig)
        serve, aqua = conf["serve"], conf["aqua"]
        e, k = conf["num_experts"], conf["num_experts_per_tok"]
        return ModelConfig(
            name=conf["name"], family="moe",
            num_layers=conf["num_hidden_layers"],
            d_model=conf["hidden_size"], d_ff=conf["moe_intermediate_size"],
            vocab_size=conf["vocab_size"],
            attention=AttentionConfig(
                num_heads=conf["num_attention_heads"],
                num_kv_heads=conf["num_key_value_heads"],
                head_dim=conf["head_dim"], qk_norm=serve["qk_norm"],
                rope_theta=float(conf["rope_theta"]),
                backend=serve["backend"]),
            # capacity for every token of a dispatch block: nothing drops
            moe=MoEConfig(num_experts=e, top_k=k,
                          expert_ff=conf["moe_intermediate_size"],
                          capacity_factor=e / k),
            norm_eps=float(conf["rms_norm_eps"]),
            tie_embeddings=bool(conf["tie_word_embeddings"]),
            act=conf["hidden_act"], dtype=serve["dtype"],
            param_dtype=serve["param_dtype"], remat=False,
            aqua=AquaConfig(**aqua))


    def init_params(conf, key):
        d, v, n = (conf["hidden_size"], conf["vocab_size"],
                   conf["num_hidden_layers"])
        h, kvh, hd = (conf["num_attention_heads"],
                      conf["num_key_value_heads"], conf["head_dim"])
        e, f = conf["num_experts"], conf["moe_intermediate_size"]
        dt = jnp.dtype(conf["serve"]["param_dtype"])
        keys = iter(jax.random.split(key, 16))

        def normal(shape, fan_in, dtype=dt):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * fan_in ** -0.5).astype(dtype)
        ones = lambda *s: jnp.ones(s, dt)
        return {
            "embed": {"table": normal((v, d), d)},
            "layers": {
                "ln1": ones(n, d), "ln2": ones(n, d),
                "attn": {"wq": normal((n, d, kvh, h // kvh, hd), d),
                         "wk": normal((n, d, kvh, hd), d),
                         "wv": normal((n, d, kvh, hd), d),
                         "wo": normal((n, kvh, h // kvh, hd, d), h * hd),
                         "q_norm": ones(n, hd), "k_norm": ones(n, hd)},
                "ffn": {"router": normal((n, d, e), d, jnp.float32),
                        "w1": normal((n, e, d, f), d),
                        "w3": normal((n, e, d, f), d),
                        "w2": normal((n, e, f, d), f)}},
            "ln_f": ones(d),
        }


    def _qkv(conf, h, at, quant, eq):
        eps = float(conf["rms_norm_eps"])
        q = R._dot(eq[0], h, at["wq"], quant, -1)
        k = R._dot(eq[1], h, at["wk"], quant, -1)
        v = R._dot(eq[1], h, at["wv"], quant, -1)
        return R._rms(q, at["q_norm"], eps), R._rms(k, at["k_norm"], eps), v


    def _moe(conf, h, ffn, quant):
        """Every expert on every row, combined by the top-k weights."""
        gates = jax.nn.softmax(R._dot("...m,me->...e", h, ffn["router"],
                                      quant, -1), axis=-1)
        w, idx = jax.lax.top_k(gates, conf["num_experts_per_tok"])
        w = w / w.sum(-1, keepdims=True)
        combine = (jax.nn.one_hot(idx, conf["num_experts"])
                   * w[..., None]).sum(-2)
        up = jax.nn.silu(R._dot("...m,emf->...ef", h, ffn["w1"], quant, -1)) \\
            * R._dot("...m,emf->...ef", h, ffn["w3"], quant, -1)
        y = R._dot("...ef,efm->...em", up, ffn["w2"], quant, -1)
        return jnp.einsum("...e,...em->...m", combine, y, precision=R.HI)


    def hidden(conf, quant, params, proj, tokens, prompt_len, q_chunk):
        positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        x = params["embed"]["table"][tokens].astype(jnp.float32)
        for i in range(conf["num_hidden_layers"]):
            weights = jax.tree.map(lambda w: w[i], params["layers"])
            x = _layer(conf, quant, x, weights, proj[i], positions,
                       prompt_len, q_chunk)
        return R._rms(x, params["ln_f"].astype(jnp.float32),
                      float(conf["rms_norm_eps"]))


    def capture(conf, params, tokens):
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        x = params["embed"]["table"][tokens].astype(jnp.float32)
        qs, ks = [], []
        for i in range(conf["num_hidden_layers"]):
            weights = jax.tree.map(lambda w: w[i], params["layers"])
            x, (q, k) = _capture_layer(conf, x, weights, positions)
            qs.append(q)
            ks.append(k)
        return jnp.stack(qs), jnp.stack(ks)


    def _layer(conf, quant, x, weights, proj, positions, prompt_len,
               q_chunk):
        eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
        p = jax.tree.map(lambda w: w.astype(jnp.float32), weights)
        q, k, v = _qkv(conf, R._rms(x, p["ln1"], eps), p["attn"], quant,
                       ("tm,mkgd->tkgd", "tm,mkd->tkd"))
        q, k = R._rope(q, positions, theta), R._rope(k, positions, theta)
        o = R.aqua_attention(conf, quant, q, k, v, proj, positions,
                             prompt_len, q_chunk)
        x = x + R._dot("tkgd,kgdm->tm", o, p["attn"]["wo"], quant,
                       (1, 2, 3))
        return x + _moe(conf, R._rms(x, p["ln2"], eps), p["ffn"], quant)


    def _capture_layer(conf, x, weights, positions):
        eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
        p = jax.tree.map(lambda w: w.astype(jnp.float32), weights)
        q, k, v = _qkv(conf, R._rms(x, p["ln1"], eps), p["attn"], None,
                       ("btm,mkgd->btkgd", "btm,mkd->btkd"))
        rope = jax.vmap(lambda z: R._rope(z, positions, theta))
        q, k = rope(q), rope(k)
        s = jnp.einsum("bskgd,btkd->bkgst", q, k, precision=R.HI) \\
            / conf["head_dim"] ** 0.5
        s = jnp.where(R.causal(positions, positions), s, R.NEG_INF)
        o = jnp.einsum("bkgst,btkd->bskgd", jax.nn.softmax(s, axis=-1), v,
                       precision=R.HI)
        x = x + jnp.einsum("bskgd,kgdm->bsm", o, p["attn"]["wo"],
                           precision=R.HI)
        return x + _moe(conf, R._rms(x, p["ln2"], eps), p["ffn"], None), \\
            (q, k)


    def shapes(conf):
        d, hd = conf["hidden_size"], conf["head_dim"]
        h, kvh = conf["num_attention_heads"], conf["num_key_value_heads"]
        e, k = conf["num_experts"], conf["num_experts_per_tok"]
        attn = d * hd * (h + 2 * kvh) + h * hd * d
        ffn = d * e + k * 3 * d * conf["moe_intermediate_size"]
        unembed = d * conf["vocab_size"]
        return Shapes(
            active=conf["num_hidden_layers"] * (attn + ffn) + unembed,
            unembed=unembed, windows=(None,) * conf["num_hidden_layers"],
            heads=h, kv_heads=kvh, head_dim=hd,
            k_dims=round_k_dims(hd, conf["aqua"]["k_ratio"],
                                conf["aqua"]["block_dims"]))
''')

CONFIG = {"architecture": "toymoe", "num_experts": 4,
          "num_experts_per_tok": 2, "moe_intermediate_size": 64}


def test_a_new_block_shape_is_files_only(tmp_path):
    name = tiny.make_tree(tmp_path, config=CONFIG, name="toymoe.chat")
    (tmp_path / "chipbench" / "arch" / "toymoe.py").write_text(TOY_MOE)
    cell = spec.load_cell(tmp_path, name)
    assert cell.arch.__file__ == str(tmp_path / "chipbench" / "arch"
                                     / "toymoe.py")
    moe = cell.arch.program_config(cell.config).moe
    assert moe.capacity_factor * moe.top_k >= moe.num_experts
    # 2 layers of attention (64*32*(4+2+2) + 4*32*64 = 24,576), router
    # 64*4 and 2 of 4 experts (2*3*64*64 = 24,576); unembedding 64*512
    assert cell.arch.shapes(cell.config).active \
        == 2 * (24_576 + 256 + 24_576) + 32_768
    res = run.run_cell(tmp_path, name, 2 ** 31 + 3, 1.5, False,
                       require_tpu=False, control=True)
    # the program's parameter tree matched the module's (run_cell checks
    # it), the served tokens match the module's reference, and the float8
    # control of the same reference does not
    assert res["correct"] is True
    assert res["attempted"] > 0
    assert res["control"]["correct"] is False
