"""Whole runs of a tiny cell on the CPU: the harness without its look for a
chip. A sound run is correct; the float8 control and a broken timed path
are not; without a TPU the command prints nothing and fails."""
import json

import numpy as np
import pytest

from chipbench import check, drive, faults, run
from chipbench.tests import tiny

SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    return root, tiny.make_tree(root)


def run_tiny(tree, traced=False, control=False, seed=SEED):
    root, name = tree
    return run.run_cell(root, name, seed, 1.5, traced, require_tpu=False,
                        control=control)


def test_sound_run_is_correct(tree):
    res = run_tiny(tree)
    assert res["correct"] is True
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "check"]
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms",
                                   "itl_p95_ms.admit", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    chk = res["check"]
    assert all(v["value"] <= v["limit"] for v in chk.values())
    assert chk["kernel_fallbacks"] == {"value": 0, "limit": 0}


def test_traced_run_reads_per_layer_metrics(tree):
    res = run_tiny(tree, traced=True)
    assert res["correct"] is True
    # no device trace and no device memory on the CPU: those readers are
    # silent, and the line leaves them out
    assert set(res["metrics"]) == {"lane_occupancy", "compiles_in_window",
                                   "ttft_p90_ms", "step_mfu"}
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_control_fails_the_limit(tree):
    res = run_tiny(tree, control=True)
    assert res["correct"] is True
    # the control in the program's place, through the harness's verdict
    assert res["control"]["correct"] is False
    shown = res["control"]["check"]
    assert any(v["value"] > v["limit"] for v in shown.values())
    r = res["readings"]
    for name, limit in tiny.CELL["check"]["limits"].items():
        assert r.number(name) <= limit
        assert shown[name]["value"] == r.number(name, control=True)


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_step_is_not_correct(tree, monkeypatch, fault):
    monkeypatch.setattr(drive, "build_engine", faults.broken(
        drive.build_engine, fault, tiny.CONFIG["vocab_size"]))
    res = run_tiny(tree)
    assert res["correct"] is False
    assert any(v["value"] > v["limit"] for v in res["check"].values())


def test_mean_gap_in_doubt_separates(tmp_path, monkeypatch):
    """The same checks for a cell that compares the mean gap in doubt: a
    sound run is correct, the float8 control and each fault are not."""
    check = dict(tiny.CELL["check"], limits=tiny.DOUBT_LIMITS)
    tree = (tmp_path, tiny.make_tree(tmp_path, cell={"check": check}))
    res = run_tiny(tree, control=True)
    assert res["correct"] is True
    assert res["control"]["correct"] is False
    build = drive.build_engine
    for fault in faults.FAULTS:
        monkeypatch.setattr(drive, "build_engine", faults.broken(
            build, fault, tiny.CONFIG["vocab_size"]))
        res = run_tiny(tree)
        assert res["correct"] is False, fault
        assert res["check"]["mean_gap_in_doubt"]["value"] \
            > tiny.DOUBT_LIMITS["mean_gap_in_doubt"]


def test_mean_gap_in_doubt_counts_doubtful_and_missed_positions():
    gaps = np.array([0.0, 0.0, 0.0, 0.3, 0.1])
    margins = np.array([0.1, 2.0, 3.0, 4.0, 0.2])
    # counted: the first (in doubt), the fourth (missed), the fifth (both)
    assert check.mean_gap_in_doubt(gaps, margins) == pytest.approx(0.4 / 3)
    assert check.mean_gap_in_doubt(np.zeros(3), np.full(3, 9.0)) == 0.0


def test_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "qwen3-0.6b.long-decode-8k", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code != 0
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err
    with pytest.raises(json.JSONDecodeError):
        json.loads(out.out or "")
