"""Finds a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix. The
files behind those names live under ``chipbench/``:

- ``configs/<config>.json``: the model configuration as it is run (the
  path is the configuration's ``file`` entry in ``BENCHMARK.json``);
- ``arch/<architecture>.py``: the block that the configuration's
  ``architecture`` names: ``program_config(conf)``, the program's
  ``ModelConfig``; ``init_params(conf, key)``, the weights from the seed in
  the program's layout; ``hidden`` and ``capture``, the float32 reference
  over every layer and its calibration pass; ``shapes(conf)``, the sizes
  behind ``step_mfu`` and the rooflines;
- ``traffic/<traffic>.json``: the traffic mix's parameters;
- ``cells/<cell>.json``: the cell's lanes, cache length and the limits of
  its output check;
- ``metrics/<metric>.py``: one reader per metric, with a ``read(run)``
  function that returns a number or None.

A cell, a mix, a metric or a block shape is added by adding files; nothing
here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
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
    arch: ModuleType      # arch/<architecture>.py
    traffic: dict         # traffic/<traffic>.json
    params: dict          # cells/<cell>.json
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(root: Path, kind: str, name: str) -> ModuleType:
    """``chipbench/<kind>/<name>.py`` under ``root``, as a module."""
    path = root / "chipbench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None:
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, name: str) -> Callable:
    """``read`` of ``chipbench/metrics/<name>.py`` under ``root``."""
    return _load_module(root, "metrics", name).read


def load_arch(root: Path, name: str) -> ModuleType:
    """The architecture module ``chipbench/arch/<name>.py`` under ``root``."""
    return _load_module(root, "arch", name)


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
                arch=load_arch(root, config["architecture"]),
                traffic=traffic, params=params,
                end_to_end=_metrics(root, bench["end_to_end"], name),
                per_layer=_metrics(root, bench["per_layer"], name))

