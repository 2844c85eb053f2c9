"""Cells on a mesh. Whole runs of a tiny cell on a 1x4 mesh of host
devices run in a process of their own (``mesh_runs.py``: JAX fixes its
device count when it starts); the plan check, the chips a cell asks for
and the one-chip serving configuration are checked here."""
import dataclasses
import json
import os
import subprocess
import sys

import pytest

from chipbench import drive, faults, run, spec
from chipbench.tests import tiny
from chipbench.tests.conftest import ROOT


@pytest.fixture(scope="module")
def mesh(tmp_path_factory):
    out = tmp_path_factory.mktemp("mesh")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.tests.mesh_runs", str(out),
         str(out / "result.json")], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads((out / "result.json").read_text())


def test_mesh_runs_on_four_devices(mesh):
    assert mesh["devices"] == 4


def test_sharded_weights_equal_the_one_device_draw(mesh):
    w = mesh["weights"]
    assert w["leaves"] > 10
    assert [k for k, same in w["equal"] if not same] == []
    assert [k for k, ok in w["sharded"] if not ok] == []
    # no device holds more than its quarter of a sharded leaf, and the
    # engine's own placement makes no second copy
    assert w["largest_shard_share"] == 0.25
    assert w["engine_put_copies"] == 0


def test_sound_mesh_run_is_correct_and_its_control_is_not(mesh):
    res = mesh["sound"]
    assert res["correct"] is True
    assert res["control"]["correct"] is False
    assert res["check"]["kernel_fallbacks"] == {"value": 0, "limit": 0}
    limit = tiny.MESH_CHECK["limits"]["mean_gap_in_doubt"]
    assert res["readings"]["mean_gap_in_doubt"] <= limit
    assert res["readings"]["control_mean_gap_in_doubt"] > limit
    dev = res["device"]
    assert dev["count"] == 4
    assert len(dev["memory_peak_bytes_per_device"]) == 4


@pytest.mark.parametrize("fault", faults.FAULTS + faults.MESH_FAULTS)
def test_broken_mesh_step_is_not_correct(mesh, fault):
    res = mesh["faults"][fault]
    assert res["correct"] is False
    limit = tiny.MESH_CHECK["limits"]["mean_gap_in_doubt"]
    assert res["check"]["mean_gap_in_doubt"]["value"] > limit


def test_a_plan_that_falls_back_on_a_mesh_fails_the_run(mesh):
    assert "did not plan the kernel path on 4 chip(s)" in mesh["fallback"]
    assert "axis extents don't divide the serving mesh" in mesh["fallback"]


def plan(**kw):
    from repro.core.dispatch import REASON_NO_MESH, DispatchPlan
    base = dict(backend="aqua-block-sparse", cache_layout="paged",
                mesh_native=False, prefix_sharing=True,
                reasons=(REASON_NO_MESH,), chunked_prefill=False,
                chunked_reasons=(), quantization="none",
                token_sparsity="none", token_reasons=())
    return DispatchPlan(**dict(base, **kw))


def test_plan_check_by_chips():
    from repro.core.dispatch import (REASON_NONDIVISIBLE_MESH,
                                     REASON_PAGE_GEOMETRY)
    be = "aqua-block-sparse"
    run.check_plan(plan(), be, 1)
    run.check_plan(plan(mesh_native=True, reasons=()), be, 4)
    bad = [(plan(), 4),                       # no mesh on a four-chip cell
           (plan(mesh_native=True, reasons=()), 1),
           (plan(reasons=(REASON_NONDIVISIBLE_MESH,)), 4),
           (plan(reasons=(REASON_PAGE_GEOMETRY,)), 1),
           (plan(mesh_native=True, reasons=(), backend="flash"), 4),
           (plan(mesh_native=True, reasons=(), cache_layout="contiguous"),
            4)]
    for p, chips in bad:
        with pytest.raises(RuntimeError, match="did not plan"):
            run.check_plan(p, be, chips)


@pytest.mark.parametrize("config,chips", [(tiny.MESH_CONFIG, 1),
                                          (tiny.CONFIG, 4)])
def test_chips_must_be_the_mesh(tmp_path, config, chips):
    name = tiny.make_tree(tmp_path, config=config, chips=chips)
    with pytest.raises(RuntimeError, match="asks for"):
        run.run_cell(tmp_path, name, 1, 0.5, False, require_tpu=False)


def test_the_four_chip_configuration_states_its_mesh():
    conf = json.loads((ROOT / "chipbench" / "configs" / "qwen1.5-4b-tp4.json")
                      .read_text())
    assert drive.mesh_shape(conf) == (1, 4) and drive.chips(conf) == 4
    one = json.loads((ROOT / "chipbench" / "configs" / "qwen1.5-4b-10l.json")
                     .read_text())
    # the same model whole: only the depth, the deployment and the mesh
    differ = {k for k in set(conf) | set(one) if conf.get(k) != one.get(k)}
    assert differ == {"name", "num_hidden_layers", "reduced", "deployment",
                      "serve"}
    assert conf["num_hidden_layers"] == 40 and conf["reduced"] == {}
    assert dict(conf["serve"], mesh_shape=None) == dict(one["serve"],
                                                        mesh_shape=None)


def one_chip_cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [w["name"] for w in bench["workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("name", one_chip_cells())
def test_one_chip_cells_serve_as_before(name):
    """The serving configuration a one-chip cell builds, against the one
    ``drive.build_engine`` built before cells could ask for a mesh."""
    from repro.configs.base import CacheSpec, ServingConfig
    cell = spec.load_cell(ROOT, name)
    conf, mix, cp = cell.config, cell.traffic, cell.params
    serve = conf["serve"]
    before = ServingConfig(
        max_lanes=cp["lanes"], max_seq=cp["max_seq"],
        temperature=mix["temperature"], prompt_bucket=mix["prompt_bucket"],
        cache=CacheSpec(page_size=serve["page_size"],
                        prefix_sharing=serve["prefix_sharing"]))
    got = drive.serving_config(cp["lanes"], cp["max_seq"], conf, mix)
    assert got == before
    assert dataclasses.asdict(got) == dataclasses.asdict(before)
    assert got.mesh_shape is None and cell.chips == 1
