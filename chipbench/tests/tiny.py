"""A tiny benchmark tree for the CPU tests: the same files a cell has,
at a size the Pallas interpreter runs in seconds."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]

CONFIG = {
    "name": "tiny", "source": "test", "model_type": "qwen3",
    "architecture": "dense",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "hidden_act": "silu", "reduced": {},
    "serve": {"qk_norm": True, "qkv_bias": False,
              "param_dtype": "bfloat16", "dtype": "bfloat16",
              "backend": "aqua-block-sparse", "page_size": 16,
              "prefix_sharing": True},
    "aqua": {"k_ratio": 0.75, "block_dims": 8, "prefill_q_blk": 16,
             "prefill_k_blk": 16, "decode_seq_blk": 16},
      "calibration": {"rows": 2, "seq": 32},
}

TRAFFIC = {
    "prompt_tokens": {"log_uniform": [20, 60]},
    "output_tokens": {"log_uniform": [3, 8]},
    "prompt_bucket": 16, "temperature": 0.0,
    "open_after_completions_per_lane": 1,
}

# the limit lies between the served path's mean gap (at most 0.0056 over
# six seeds) and the float8 control's (at least 0.040) at this size
CELL = {"lanes": 2, "max_seq": 80,
        "check": {"from": "served", "tokens": 400, "max_requests": 64,
                  "limits": {"mean_gap": 0.015}}}

# the limit lies between the served path's mean gap in doubt (at most
# 0.0075 over eight seeds) and the float8 control's (at least 0.064) at
# this size; state_unchanged reads 0.31 and token_altered 2.3
DOUBT_LIMITS = {"mean_gap_in_doubt": 0.025}


# the tiny block on a 1x4 mesh: heads that divide four ways (MHA, 4 KV
# heads, G = 1), with q/k/v biases and an untied unembedding, as
# qwen1.5-4b-tp4 has them
MESH_CONFIG = dict(
    CONFIG, num_key_value_heads=4, tie_word_embeddings=False,
    serve=dict(CONFIG["serve"], qk_norm=False, qkv_bias=True,
               mesh_shape=[1, 4]))

# the mesh cell's limit lies between the served path's mean gap in doubt
# (at most 0.0028 over ten seeds) and the float8 control's (at least
# 0.0178) on the 1x4 mesh of host devices, with MESH_TRAFFIC and a 1 s
# window
MESH_CHECK = dict(CELL["check"], limits={"mean_gap_in_doubt": 0.007})

# longer outputs for the mesh cell (and the cache to hold them), so that
# its window holds decode steps in every lane and its backlog outlasts the
# window on a fast host
MESH_TRAFFIC = {"output_tokens": {"log_uniform": [8, 24]}}
MESH_CELL = {"max_seq": 96, "check": MESH_CHECK}


def make_tree(root: Path, config=None, traffic=None, cell=None,
              name: str = "tiny.chat", chips: int = 1) -> str:
    """Write BENCHMARK.json and the cell's files under ``root``; return
    the cell's name. The metric readers and the architecture modules are
    copied from this benchmark."""
    conf = dict(CONFIG, **(config or {}))
    cfg_name, mix_name = name.split(".", 1)
    conf["name"] = cfg_name
    (root / "chipbench" / "configs").mkdir(parents=True, exist_ok=True)
    (root / "chipbench" / "traffic").mkdir(parents=True, exist_ok=True)
    (root / "chipbench" / "cells").mkdir(parents=True, exist_ok=True)
    for kind in ("metrics", "arch"):
        if not (root / "chipbench" / kind).exists():
            shutil.copytree(HERE / kind, root / "chipbench" / kind)
    (root / "chipbench" / "configs" / f"{cfg_name}.json").write_text(
        json.dumps(conf))
    (root / "chipbench" / "traffic" / f"{mix_name}.json").write_text(
        json.dumps(dict(TRAFFIC, **(traffic or {}))))
    (root / "chipbench" / "cells" / f"{name}.json").write_text(
        json.dumps(dict(CELL, **(cell or {}))))
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": cfg_name, "source": "test",
                         "file": f"chipbench/configs/{cfg_name}.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": name, "config": cfg_name,
                           "traffic": mix_name, "chips": chips,
                           "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name
