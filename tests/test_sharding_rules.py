"""Unit tests for the sharding rules (divisibility fallbacks) using an
AbstractMesh (no devices needed)."""
import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed import sharding as sh


MESH = AbstractMesh((16, 16), ("data", "model"))
POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _key(name):
    return (jax.tree_util.DictKey(name),)


def test_wq_shards_kv_heads_when_divisible():
    # stacked (L, dm, KV=16, G, D)
    spec = sh.param_pspec(_key("wq"), (24, 2048, 16, 1, 128), MESH)
    assert spec == P(None, None, "model", None, None)


def test_wq_falls_back_to_groups_for_gqa8():
    # KV=8 doesn't divide 16 -> try G=4 (fails) -> replicated
    spec = sh.param_pspec(_key("wq"), (24, 2560, 8, 4, 80), MESH)
    assert spec == P(None, None, None, None, None)


def test_wq_mqa_uses_group_axis():
    # MQA: KV=1, G=16 -> shard groups
    spec = sh.param_pspec(_key("wq"), (38, 4096, 1, 16, 256), MESH)
    assert spec == P(None, None, None, "model", None)


def test_wk_falls_back_to_head_dim():
    spec = sh.param_pspec(_key("wk"), (38, 4096, 1, 256), MESH)
    assert spec == P(None, None, None, "model")


def test_moe_expert_parallel_when_divisible():
    spec = sh.param_pspec(_key("w1"), (16, 64, 2048, 1024), MESH)
    assert spec == P(None, "model", None, None)


def test_moe_falls_back_to_ff_tp_for_60_experts():
    spec = sh.param_pspec(_key("w1"), (24, 60, 2048, 1408), MESH)
    assert spec == P(None, None, None, "model")


def test_embedding_vocab_sharding_and_fallback():
    assert sh.param_pspec(_key("table"), (151936, 1024), MESH) == \
        P("model", None)
    # 50280 % 16 != 0 -> shard d_model instead
    assert sh.param_pspec(_key("table"), (50280, 1024), MESH) == \
        P(None, "model")
    # 51865 odd and 384 % 16 == 0 -> d_model
    assert sh.param_pspec(_key("table"), (51865, 384), MESH) == \
        P(None, "model")


def test_norms_replicated():
    assert sh.param_pspec(_key("ln1"), (24, 2048), MESH) == P(None, None)
    assert sh.param_pspec(_key("lam"), (4096,), MESH) == P(None)


def test_one_dim_param_never_crashes():
    # regression: eager candidate construction crashed on 1-D params
    assert sh.param_pspec(_key("conv_b"), (4096,), MESH) == P("model")


def test_sanitize_drops_nondivisible():
    assert sh.sanitize(P("model", None), (60, 4), MESH) == P(None, None)
    assert sh.sanitize(P(("pod", "data"), None), (64, 4), POD) == \
        P(("pod", "data"), None)
    assert sh.sanitize(P(("pod", "data"), None), (16, 4), POD) == \
        P(None, None)


def test_sanitize_batch1_single_kv_serving_cache():
    """Serving cache shapes at the degenerate corners: a single decode
    lane (B=1) and MQA (KV=1) must drop every non-dividing axis
    independently — never crash, never leave a stale axis behind."""
    serving = AbstractMesh((4, 2), ("data", "model"))
    # k (L, B, KV, S, D) with B=1 and KV=1: batch/kv axes drop, slots=64
    # absorb data×model (8 | 64)
    spec = sh.decode_state_pspec(_key("k"), (2, 1, 1, 64, 8), serving,
                                 kv_shardable=False, batch_shardable=False)
    assert spec == P(None, None, None, ("data", "model"), None)
    # tiny slot count (4 < 8): nothing divides -> fully replicated
    spec = sh.decode_state_pspec(_key("k"), (2, 1, 1, 4, 8), serving,
                                 kv_shardable=False, batch_shardable=False)
    assert spec == P(None, None, None, None, None)
    # H2O acc_score (L, B, KV, S) at B=1/KV=1 follows the same fallback
    spec = sh.decode_state_pspec(_key("acc_score"), (2, 1, 1, 64), serving,
                                 kv_shardable=False, batch_shardable=False)
    assert spec == P(None, None, None, ("data", "model"))
    # sanitize itself: every entry of a (1, 1) shape drops
    assert sh.sanitize(P(("data", "model"), "model"), (1, 1), serving) == \
        P(None, None)


def test_lane_pspec_divisibility():
    serving = AbstractMesh((4, 2), ("data", "model"))
    assert sh.lane_pspec(serving, 8) == P(("data",))
    assert sh.lane_pspec(serving, 1) == P(None)    # single lane: replicate
    assert sh.lane_pspec(serving, 6) == P(None)    # 4 does not divide 6
    modelonly = AbstractMesh((2,), ("model",))
    assert sh.lane_pspec(modelonly, 8) == P(None)  # no data axes at all


def test_batch_pspec_multi_pod():
    assert sh.batch_pspec(POD, (256, 4096)) == P(("pod", "data"), None)
    # B=16: can't use pod*data=32 -> falls back to data only
    assert sh.batch_pspec(POD, (16, 4096)) == P("data", None)
    # B=1 (long_500k): replicated
    assert sh.batch_pspec(POD, (1, 1)) == P(None, None)


def test_decode_state_kv_fallback_to_slots():
    # stacked cache (L, B, KV=8, S, D): KV not divisible -> slots on model
    spec = sh.decode_state_pspec(_key("k"), (24, 128, 8, 32768, 64), MESH,
                                 kv_shardable=False, batch_shardable=True)
    assert spec == P(None, ("data",), None, "model", None)


def test_decode_state_long_context_batch1():
    spec = sh.decode_state_pspec(_key("k"), (24, 1, 8, 4096, 64), MESH,
                                 kv_shardable=False, batch_shardable=False)
    assert spec == P(None, None, None, ("data", "model"), None)


def test_decode_state_kv_shardable():
    spec = sh.decode_state_pspec(_key("k"), (16, 128, 16, 32768, 128), MESH,
                                 kv_shardable=True, batch_shardable=True)
    assert spec == P(None, ("data",), "model", None, None)


def test_ssm_state_spec():
    spec = sh.decode_state_pspec(_key("state"), (48, 128, 32, 64, 128), MESH,
                                 kv_shardable=False, batch_shardable=True)
    assert spec == P(None, ("data",), "model", None, None)


def test_dryrun_collective_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
      %ag = f32[16,128]{1,0} all-gather(f32[1,128] %x), dim=0
      %ar = bf16[1024]{0} all-reduce(bf16[1024] %y), to_apply=%add
      %ars = f32[8,8]{1,0} all-reduce-start(f32[8,8] %z), to_apply=%add
      %cp = u8[64]{0} collective-permute(u8[64] %w)
      %a2a = f32[4,4]{1,0} all-to-all(f32[4,4] %v)
    """
    cb = collective_bytes(hlo)
    assert cb["all-gather"] == 16 * 128 * 4
    assert cb["all-reduce"] == 1024 * 2 + 8 * 8 * 4
    assert cb["collective-permute"] == 64
    assert cb["all-to-all"] == 64


def test_input_specs_cover_all_cells():
    from repro.configs import ASSIGNED_ARCHS, SHAPES_BY_NAME, get_config
    from repro.launch.dryrun import input_specs
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for sname, shp in SHAPES_BY_NAME.items():
            if sname == "long_500k" and cfg.skip_long_context:
                continue
            specs = input_specs(cfg, shp)
            assert "params" in specs
            if shp.mode == "decode":
                assert specs["tokens"].shape == (shp.global_batch,)
            else:
                assert specs["batch"]["tokens"].shape == (
                    shp.global_batch, shp.seq_len)


def test_chip_peaks_keyed_by_device_kind():
    from repro.launch.mesh import chip_peaks

    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(KeyError, match="TPU v4"):
        chip_peaks("TPU v4")       # unknown kinds raise, never default
