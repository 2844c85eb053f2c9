"""The dense block's weights, calibrated projections, reference hidden
states and counts, pinned bit for bit: at the tiny test size, at the tiny
size with q/k/v biases and an untied unembedding, and at qwen3-0.6b's
widths with two layers. The digests were taken before the block moved into
``arch/dense.py``; a change that moves any of them changes what every
qwen3-0.6b cell serves and compares."""
import hashlib
import json

import jax
import numpy as np
import pytest

from chipbench import reference, spec
from chipbench.tests import tiny
from chipbench.tests.conftest import ROOT
from chipbench.yardstick import (aqua_decode_cost, aqua_prefill_cost,
                                 decode_token_flops, prefill_flops)

QWEN3 = json.loads((ROOT / "chipbench" / "configs" / "qwen3-0.6b.json")
                   .read_text())
CONFS = {
    "tiny": tiny.CONFIG,
    "tiny-bias-untied": dict(
        tiny.CONFIG, name="tiny-bias-untied", tie_word_embeddings=False,
        serve=dict(tiny.CONFIG["serve"], qk_norm=False, qkv_bias=True)),
    "qwen3-0.6b-2l": dict(QWEN3, name="qwen3-0.6b-2l", num_hidden_layers=2),
}
SEEDS = (0, 7, 2 ** 31 + 11)

PINNED = {
    "tiny": {
        "weights": ["46f5cfc71074c409ee3636f9e29c3ef2",
                    "1b601b429b47ed009ded5ad167fe6a38",
                    "eebb1d4ea6476ee1efd326ab31b3bb08"],
        "proj": "7802293159d78d40b70b1a906d8a8c48",
        "hidden": "722f4f76e22c97f15d0269c013bdbc26",
        "hidden_fp8": "fbc848fc75b5bd0820726ba245f18afa",
        "counts": [131072, (896000.0, 449024.0), (40454400.0, 403200.0),
                   1158144.0, 99502336.0]},
    "tiny-bias-untied": {
        "weights": ["b1112c9f0be238556236a91ba1756224",
                    "44ac8f88dd3b01db69ee81a09e64cd8b",
                    "91bdb7b3243d6f41a02553118c9ffdd5"],
        "proj": "06836163fc487a2a98a9bc6726750b79",
        "hidden": "5b1c4a03c6906b0e642bbb57a84a20a5",
        "hidden_fp8": "c8c873b0ed7d549ecbded71584db6452",
        "counts": [131072, (896000.0, 449024.0), (40454400.0, 403200.0),
                   1158144.0, 99502336.0]},
    "qwen3-0.6b-2l": {
        "weights": ["b19110ba7da35616bca30134ea528077",
                    "f30bdeb9bba60cb95e0085a5ea4d609d",
                    "4384fdd8babe2d1306b6052d8af7842d"],
        "proj": "257ce125dec9c747f3aca67a0aeb40eb",
        "hidden": "ec1efffab3a4900002611ac65e78ed65",
        "hidden_fp8": "b1998fc7f1898903322b302c03339c73",
        "counts": [187039744, (14336000.0, 7184384.0),
                   (647270400.0, 6451200.0), 388415488.0, 19832803328.0]},
}


def digest(tree) -> str:
    """Every leaf's path, dtype, shape and bytes, in tree order."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()[:32]


@pytest.mark.parametrize("name", list(CONFS))
def test_dense_block_is_pinned(name):
    conf, want = CONFS[name], PINNED[name]
    arch = spec.load_arch(ROOT, conf["architecture"])
    init = jax.jit(lambda k: arch.init_params(conf, k))
    assert [digest(init(reference.weights_key(s))) for s in SEEDS] \
        == want["weights"]
    params = init(reference.weights_key(7))
    proj = reference.calibrate(arch, conf, params, reference.corpus_tokens(
        str(ROOT / "chipbench" / "data" / "calibration.txt"),
        conf["vocab_size"], **conf["calibration"]))
    assert digest(proj) == want["proj"]
    q_chunk = max(conf["aqua"]["prefill_q_blk"], 32)
    t = 2 * q_chunk
    seq = np.random.default_rng(5).integers(
        0, conf["vocab_size"], t).astype(np.int32)
    for key, quant in (("hidden", None), ("hidden_fp8", "fp8")):
        h = reference.hidden(arch, conf, params, proj, seq, t // 2 + 3,
                             quant=quant, q_chunk=q_chunk)
        assert digest(h) == want[key], key
    s = arch.shapes(conf)
    assert [s.active, aqua_decode_cost(s, 1000), aqua_prefill_cost(s, 300),
            decode_token_flops(s, 1000), prefill_flops(s, 300)] \
        == want["counts"]
