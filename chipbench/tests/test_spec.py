"""Every cell of BENCHMARK.json resolves to its files by name, a cell added
as files alone is picked up, and the file keeps to the benchmark's format."""
import json
import re

import pytest

from chipbench import drive, spec, traffic
from chipbench.tests import tiny
from chipbench.tests.conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = spec.load_cell(ROOT, name)
    w = next(w for w in BENCH["workloads"] if w["name"] == name)
    assert cell.config["name"] == w["config"]
    assert cell.chips == w["chips"] == drive.chips(cell.config)
    assert set(cell.params) == {"lanes", "max_seq", "check"}
    assert cell.params["check"]["limits"], "a cell needs a limit"
    names = {m.name for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    # every request of the mix fits the cell's cache
    lanes = cell.params["lanes"]
    for p in traffic.closed_loop(cell.traffic, lanes, 3, 1000)[:4 * lanes]:
        assert len(p.tokens) + p.max_new <= cell.params["max_seq"]


@pytest.mark.parametrize("name", CELLS)
def test_model_config_matches_the_file(name):
    cell = spec.load_cell(ROOT, name)
    cfg = cell.arch.program_config(cell.config)
    c = cell.config
    assert (cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size) == (
        c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"],
        c["vocab_size"])
    assert cfg.attention.num_heads * cfg.attention.head_dim == (
        c["num_attention_heads"] * c["head_dim"])
    assert cfg.attention.backend == "aqua-block-sparse"


def test_a_cell_added_as_files_alone_is_picked_up(tmp_path):
    name = tiny.make_tree(tmp_path, name="tinier.longer",
                          traffic={"prompt_tokens": {"log_uniform": [30, 40]}},
                          cell={"lanes": 3})
    cell = spec.load_cell(tmp_path, name)
    assert cell.config["name"] == "tinier"
    assert cell.traffic["prompt_tokens"] == {"log_uniform": [30, 40]}
    assert cell.params["lanes"] == 3
    # a new per-layer metric is one more reader file, found by its name
    (tmp_path / "chipbench" / "metrics" / "answer.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "answer", "unit": "count",
                               "better": "higher", "source": "program_counter",
                               "layer": "engine", "moves": "tokens_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    got = {m.name: m for m in spec.load_cell(tmp_path, name).per_layer}
    assert got["answer"].read(None) == 42.0


def test_every_metric_has_a_reader():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").exists()


def test_benchmark_file_format():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")
        assert all(NAME.match(k) for k in c["reduced"])
        conf = json.loads((ROOT / c["file"]).read_text())
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
    configs = {c["name"] for c in BENCH["configs"]}
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(BENCH["workloads"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert next(m for m in BENCH["end_to_end"]
                if m["name"] == "setup_s")["bound"] == 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert set(m["workloads"]) <= set(CELLS)
        # every cell it lists reports the metric it moves
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", CELLS))
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("name", CELLS)
def test_traffic_draws_lengths_from_the_seed(name):
    cell = spec.load_cell(ROOT, name)
    lanes, mix = cell.params["lanes"], cell.traffic
    runs = [traffic.closed_loop(mix, lanes, s, 1000)
            for s in (3, 2 ** 31 + 7)]
    lengths = [[(len(p.tokens), p.max_new) for p in r] for r in runs]
    if "length_seed" in mix:
        # the lengths come from the mix's own seed: every run serves the
        # same lengths in the same order, and the seed draws the tokens
        assert lengths[0] == lengths[1]
        assert any((a.tokens != b.tokens).any() for a, b in zip(*runs))
    else:
        firsts = [sorted(n for n, _ in ls[:lanes]) for ls in lengths]
        assert firsts[0] != firsts[1], "every seed serves the same prompts"
    (lo, hi), = mix["prompt_tokens"].values()
    for r in runs:
        # a wave takes one length from each of the lanes' strata
        for w in range(0, len(r), lanes):
            got = sorted(len(p.tokens) for p in r[w:w + lanes])
            assert all(lo < n <= hi for n in got)
            edges = [lo * (hi / lo) ** (i / lanes) for i in range(lanes + 1)]
            assert all(edges[i] - 1 <= n <= edges[i + 1] + 1
                       for i, n in enumerate(got))


@pytest.mark.parametrize("name", CELLS)
def test_first_wave_compiles_every_prefill_the_window_uses(name):
    """Set-up admits the first wave; every later admission has to reuse one
    of its prompt buckets, or it would compile inside the window."""
    cell = spec.load_cell(ROOT, name)
    lanes, mix = cell.params["lanes"], cell.traffic
    bucket = mix["prompt_bucket"]
    for seed in (3, 11, 2 ** 31 + 7, 2 ** 33 + 1):
        r = traffic.closed_loop(mix, lanes, seed, 1000)
        shape = lambda p: -(-len(p.tokens) // bucket)
        assert {shape(p) for p in r} <= {shape(p) for p in r[:lanes]}
