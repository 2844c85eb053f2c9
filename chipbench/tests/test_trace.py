"""The trace reduction: busy union, idle share, kernel and module sums and the
breakdown, on a hand-made record and on a record trimmed from a chip trace
of qwen3-0.6b.long-decode-8k (TPU v5e, six engine calls)."""
import pytest

from chipbench import spec, trace
from chipbench.tests.conftest import ROOT
from chipbench.tests.test_metrics import run_of, window

RECORDED = ROOT / "chipbench" / "tests" / "trace_qwen3-0.6b.long-decode-8k.json.gz"


def hand_made():
    # host spans cover [100, 400] ns; ops overlap and one starts before
    return {"devices": {"/device:TPU:0": {
        "ops": [["%while.1", 100.0, 250.0],          # container: not ranked
                ["%aqua_paged_decode_attention.6", 110.0, 100.0],
                ["%fusion.3", 150.0, 100.0],          # overlaps the kernel
                ["%copy.2", 300.0, 50.0],
                ["%early", 0.0, 120.0]],              # clipped to [100, 120]
        "modules": [["jit__step_impl(1)", 100.0, 160.0],
                    ["jit__step_impl(1)", 300.0, 60.0]]}},
        "host": [["chipbench.next", 100.0, 200.0, 0],
                 ["chipbench.next", 300.0, 100.0, 1]]}


def test_busy_union_and_idle_share():
    rec = hand_made()
    assert trace.window(rec) == (100.0, 400.0)
    assert trace.merged_busy(rec, "/device:TPU:0") == [(100.0, 350.0)]
    assert trace.busy_seconds(rec) == pytest.approx(250e-9)
    assert trace.window_seconds(rec) == pytest.approx(300e-9)
    got = spec.load_reader(ROOT, "device_idle_share")(
        run_of(window({}, t_open=0, t_close=1), trace=rec))
    assert got == pytest.approx(100 * 50 / 300)


def test_kernel_and_module_sums():
    rec = hand_made()
    ev = trace.matching(rec, "ops", lambda n: n.startswith(
        "%aqua_paged_decode_attention"))
    assert trace.seconds_of(ev) == pytest.approx(100e-9)
    run = run_of(window({}, t_open=0, t_close=1), trace=rec)
    assert spec.load_reader(ROOT, "decode_step_ms")(run) == pytest.approx(
        1e3 * 110e-9)


def test_breakdown():
    rec = hand_made()
    top = trace.top_ops(rec, 3)
    assert [n for n, _ in top] == ["%aqua_paged_decode_attention.6",
                                   "%fusion.3", "%copy.2"]
    assert top[0][1] == pytest.approx(100e-9)
    gaps = trace.idle_gaps(rec, {1: "engine: decode step"})
    assert gaps == [["engine: decode step", pytest.approx(50e-9)]]


def test_recorded_trace():
    rec = trace.load(str(RECORDED))
    assert list(rec["devices"]) == ["/device:TPU:0"]
    busy, win = trace.busy_seconds(rec), trace.window_seconds(rec)
    assert 0 < busy < win
    assert busy == pytest.approx(0.507634865)
    assert win == pytest.approx(0.515802229)
    top = trace.top_ops(rec)
    assert top[0][0].startswith("%aqua_paged_decode_attention")
    assert len(top) == 10 and not any(
        n.startswith(trace.CONTAINERS) for n, _ in top)
    gaps = trace.idle_gaps(rec)
    assert len(gaps) == 10
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)
    assert gaps[0][1] == pytest.approx(0.003923623)
    # two decode steps, each under one module event
    mods = trace.matching(rec, "modules", lambda n: n.startswith("jit__step"))
    assert len(mods) == 2


def test_trim_keeps_the_first_spans():
    rec = trace.trim(hand_made(), 1)
    assert rec["host"] == [["chipbench.next", 100.0, 200.0, 0]]
    assert trace.window(rec) == (100.0, 300.0)
    assert all(e[1] < 300 for e in rec["devices"]["/device:TPU:0"]["ops"])
