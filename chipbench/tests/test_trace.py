"""The trace reduction: busy union, idle share, kernel and module sums, the
breakdown and the collectives, on hand-made records (one device, and two
devices of a mesh) and on a record trimmed from a chip trace of
qwen3-0.6b.long-decode-8k (TPU v5e, six engine calls)."""
import pytest

from chipbench import spec, trace
from chipbench.tests.conftest import ROOT
from chipbench.tests.test_metrics import SHAPES, run_of, window
from chipbench.drive import Unit
from chipbench.yardstick import (aqua_decode_cost, chip_peaks,
                                 least_seconds)

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


def two_devices():
    """Two devices of a mesh over one window [0, 1000] ns, two decode
    steps each. TPU:0 runs an all-reduce inside each step, half of the
    first under a fusion; an all-gather outside any step; an async pair.
    TPU:1 runs one synchronous all-reduce a step, never overlapped."""
    step = "jit__step_impl(7)"
    return {"devices": {
        "/device:TPU:0": {
            "ops": [["%while.3", 0.0, 900.0],             # container
                    ["%aqua_paged_decode_attention.1", 10.0, 100.0],
                    ["%all-reduce.5", 110.0, 40.0],       # 20 under fusion
                    ["%fusion.9", 130.0, 50.0],
                    ["%all-gather.2", 450.0, 30.0],       # no step: left out
                    ["%aqua_paged_decode_attention.1", 510.0, 100.0],
                    ["%all-reduce-start.4", 610.0, 5.0],
                    ["%fusion.11", 615.0, 60.0],
                    ["%all-reduce-done.4", 675.0, 25.0]],
            "modules": [[step, 0.0, 400.0], ["jit__admit(1)", 420.0, 80.0],
                        [step, 500.0, 400.0]]},
        "/device:TPU:1": {
            "ops": [["%aqua_paged_decode_attention.1", 10.0, 100.0],
                    ["%all-reduce.5", 110.0, 60.0],
                    ["%aqua_paged_decode_attention.1", 510.0, 100.0],
                    ["%all-reduce.5", 610.0, 60.0]],
            "modules": [[step, 0.0, 400.0], [step, 500.0, 400.0]]}},
        "host": [["chipbench.next", 0.0, 1000.0, 0]]}


@pytest.mark.parametrize("name,want", [
    ("%all-reduce.5", True), ("%all-reduce-start.4", True),
    ("%all-reduce-done.4", True), ("%all-gather.2", True),
    ("%reduce-scatter.1", True), ("%collective-permute-done.3", True),
    ("%all-to-all.7", True), ("%fusion.9", False),
    ("%aqua_paged_decode_attention.1", False), ("%all-reduce", True)])
def test_collective_names(name, want):
    assert trace.is_collective(name) is want


def test_collectives_per_step_on_two_devices():
    rec = two_devices()
    # TPU:0: all-reduce 40 (20 exposed) + start 5 + done 25, both exposed,
    # over 2 steps; TPU:1: 2 x 60, all exposed, over 2 steps
    coll, exposed = trace.collectives_per_step(rec, ("jit__step_impl",))
    assert coll == pytest.approx(((70 / 2) + (120 / 2)) / 2 * 1e-9)
    assert exposed == pytest.approx(((50 / 2) + (120 / 2)) / 2 * 1e-9)
    run = run_of(window({}, t_open=0, t_close=1), trace=rec, chips=2)
    assert spec.load_reader(ROOT, "collective_ms")(run) == pytest.approx(
        1e3 * coll)
    assert spec.load_reader(ROOT, "collective_exposed_ms")(run) == \
        pytest.approx(1e3 * exposed)


def test_a_one_chip_trace_has_no_collectives():
    run = run_of(window({}, t_open=0, t_close=1), trace=hand_made())
    assert trace.collectives_per_step(hand_made(), ("jit__step_impl",)) \
        is None
    assert spec.load_reader(ROOT, "collective_ms")(run) is None
    assert spec.load_reader(ROOT, "collective_exposed_ms")(run) is None


def test_mesh_roofline_is_chip_seconds_over_chip_seconds():
    """On two devices the kernel's time is summed over both: each runs
    half the heads, so the share is what one device alone would read for
    the whole work in the summed time."""
    rec = two_devices()
    units = [Unit("step", 0.5, [(0, 10), (1, 20)])]
    run = run_of(window({}, t_open=0, t_close=1, units=units), trace=rec,
                 prompt_len={0: 300, 1: 500}, chips=2)
    flops = nbytes = 0.0
    for n in (310, 520):
        f, b = aqua_decode_cost(SHAPES, n)
        flops, nbytes = flops + f, nbytes + b
    least = least_seconds(flops, nbytes, chip_peaks("TPU v5 lite"))
    got = spec.load_reader(ROOT, "aqua_decode_roofline")(run)
    # four kernel events of 100 ns over the two devices
    assert got == pytest.approx(100 * least / 400e-9)
    # a step's device time stays a mean per device event: 4 x 400 ns / 4
    assert spec.load_reader(ROOT, "decode_step_ms")(run) == pytest.approx(
        1e3 * 400e-9)


def test_a_profiler_line_becomes_arrays():
    """``extract``'s pass over a line: full instruction names that share a
    short name count as one, and the rows come back as they went in."""
    class Event:
        def __init__(self, name, start, dur):
            self.name, self.start_ns, self.duration_ns = name, start, dur

    class ProfLine:
        events = [Event("%fusion.3 = bf16[4] fusion(%a)", 10.0, 5.0),
                  Event("%all-reduce.5 = bf16[4] all-reduce(%b)", 15.0, 2.0),
                  Event("%fusion.3 = bf16[4] fusion(%c)", 20.0, 5.0)]
    ln = trace._read_line(ProfLine())
    assert ln.names == ["%fusion.3", "%all-reduce.5"]
    assert ln.rows() == [["%fusion.3", 10.0, 5.0], ["%all-reduce.5", 15.0, 2.0],
                         ["%fusion.3", 20.0, 5.0]]
    assert trace.Line.of(ln.rows()).rows() == ln.rows()
    assert ln.where(trace.is_collective).tolist() == [False, True, False]
