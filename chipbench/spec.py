"""Finds a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix. The
files behind those names live under ``chipbench/``:

- ``configs/<config>.json``: the model configuration as it is run (the
  path is the configuration's ``file`` entry in ``BENCHMARK.json``);
- ``traffic/<traffic>.json``: the traffic mix's parameters;
- ``cells/<cell>.json``: the cell's lanes, cache length and the limits of
  its output check;
- ``metrics/<metric>.py``: one reader per metric, with a ``read(run)``
  function that returns a number or None.

A cell, a mix or a metric is added by adding files; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: Callable


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<traffic>.json
    params: dict          # cells/<cell>.json
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(root: Path, name: str) -> Callable:
    """``read`` of ``chipbench/metrics/<name>.py`` under ``root``."""
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None:
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics(root: Path, entries: list, cell: str) -> List[Metric]:
    out = []
    for m in entries:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        out.append(Metric(m["name"], m["unit"], load_reader(root, m["name"])))
    return out


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with all its files."""
    bench = _load_json(root / "BENCHMARK.json")
    cells: Dict[str, dict] = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(root / "chipbench" / "traffic" / f"{w['traffic']}.json")
    params = _load_json(root / "chipbench" / "cells" / f"{name}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, params=params,
                end_to_end=_metrics(root, bench["end_to_end"], name),
                per_layer=_metrics(root, bench["per_layer"], name))


def model_config(conf: dict):
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
