"""The operations and bytes behind step_mfu and the AQUA rooflines, at the
configurations' shapes, against values worked by hand."""
import json

import pytest

from chipbench import spec
from chipbench.tests.conftest import ROOT
from chipbench.yardstick import (CHIP_PEAKS, Shapes, aqua_decode_cost,
                                 aqua_prefill_cost, attention_flops,
                                 chip_peaks, decode_token_flops,
                                 least_seconds, prefill_flops, round_k_dims)


def shapes(name):
    with open(ROOT / "chipbench" / "configs" / f"{name}.json") as f:
        conf = json.load(f)
    return spec.load_arch(ROOT, conf["architecture"]).shapes(conf)


def test_k_dims_round_to_whole_blocks():
    assert round_k_dims(128, 0.75, 8) == 96
    assert round_k_dims(128, 0.7, 8) == 96      # 89.6 -> 90 -> 96
    assert round_k_dims(128, 0.01, 8) == 8
    assert round_k_dims(128, 1.0, 8) == 128


def test_qwen3_0_6b_counts():
    s = shapes("qwen3-0.6b")
    # per layer: q,k,v 1024*128*(16+8+8) + o 16*128*1024 + mlp 3*1024*3072
    #          = 4,194,304 + 2,097,152 + 9,437,184 = 15,728,640
    # 28 layers + unembedding 1024*151,936 = 155,582,464
    assert s.active == 28 * 15_728_640 + 155_582_464 == 595_984_384
    # one query over 8192 keys: 2 * 28 layers * 16 heads * 8192 * (96 + 128)
    assert attention_flops(s, s.decode_keys(8192)) == 1_644_167_168
    # decode kernel bytes at 8192: 28 * (8 KV heads * 8192 * 224
    #   + q and out 2 * 16 * 128) * 2 B = 28 * 14,684,160 * 2
    assert aqua_decode_cost(s, 8192) == (1_644_167_168, 822_312_960)
    assert decode_token_flops(s, 8192) == 2 * 595_984_384 + 1_644_167_168
    # short-chat's longest prompt, 1024: causal keys 1024*1025/2 = 524,800;
    # 2 * 28 * 16 * 524,800 * 224 FLOPs; bytes 28 * 1024 * (16 + 8) * 224 * 2
    assert aqua_prefill_cost(s, 1024) == (105_329_459_200, 308_281_344)
    # prefill: every token through the layers, the unembedding once
    assert prefill_flops(s, 1024) == (2 * 28 * 15_728_640 * 1024
                                      + 2 * 155_582_464 + 105_329_459_200)


def test_mha_counts():
    # Qwen1.5-4B's layers at 10 of its 40: one query head per KV head
    s = shapes("qwen1.5-4b-10l")
    # per layer: 2560*128*(20+20+20) + 20*128*2560 + 3*2560*6912
    #          = 19,660,800 + 6,553,600 + 53,084,160 = 79,298,560
    assert s.active == 10 * 79_298_560 + 2560 * 151_936 \
        == 1_181_941_760
    # 2 * 10 * 20 * 8192 * 224 FLOPs; 10 * (20 * 8192 * 224 + 2 * 20 * 128)
    # * 2 B of bytes
    assert aqua_decode_cost(s, 8192) == (734_003_200, 734_105_600)


def test_windowed_layers_count_only_their_window():
    # one full layer and one that sees the last 100 keys
    s = Shapes(active=1000, unembed=100, windows=(None, 100), heads=4,
               kv_heads=2, head_dim=32, k_dims=24)
    assert s.layers == 2
    assert s.decode_keys(50) == 100 and s.decode_keys(300) == 400
    # prefill of 300: full 300*301/2 = 45,150; windowed: tokens 1-100 see
    # 1..100 (5,050), the other 200 see 100 each (20,000)
    assert s.causal_keys(300) == 45_150 + 25_050
    assert s.causal_keys(80) == 2 * 80 * 81 / 2
    assert aqua_decode_cost(s, 300) == (
        2.0 * 4 * 400 * 56, float((2 * 400 * 56 + 2 * 2 * 4 * 32) * 2))
    assert prefill_flops(s, 300) == (2.0 * 900 * 300 + 2.0 * 100
                                     + 2.0 * 4 * 70_200 * 56)


def test_least_time_is_the_larger_bound():
    p = chip_peaks("TPU v5 lite")
    assert least_seconds(197e12, 0, p) == pytest.approx(1.0)
    assert least_seconds(0, 819e9, p) == pytest.approx(1.0)
    assert least_seconds(197e12, 2 * 819e9, p) == pytest.approx(2.0)


def test_unknown_chip_raises():
    assert set(CHIP_PEAKS) == {"TPU v5 lite"}
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("TPU v4")
