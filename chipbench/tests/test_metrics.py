"""The end-to-end and engine readers on hand-built windows: percentiles over
all samples, closed-loop TTFT attribution, rates and occupancy."""
import numpy as np
import pytest

from chipbench import spec
from chipbench.drive import Unit, Window, itl_gaps, ttfts
from chipbench.record import Run
from chipbench.tests import tiny
from chipbench.tests.conftest import ROOT
from chipbench.yardstick import (chip_peaks, decode_token_flops,
                                 device_peaks, fullest, prefill_flops)

SHAPES = spec.load_arch(ROOT, tiny.CONFIG["architecture"]).shapes(
    tiny.CONFIG)


def read(name, run):
    return spec.load_reader(ROOT, name)(run)


def window(times, *, t_open, t_close, units=(), finished=None,
           completion_order=(), steps=(0, 0), occupancy=(0, 0)):
    """A window from {uid: [(time, index), ...]}."""
    return Window(t_open=t_open, t_close=t_close, units=list(units),
                  times=times, served={u: [0] * len(t) for u, t in times.items()},
                  finished=finished or {}, completion_order=list(completion_order),
                  admission_order=sorted(times), steps_open=steps[0],
                  occupancy_open=occupancy[0], steps_close=steps[1],
                  occupancy_close=occupancy[1], labels={})


def run_of(win, lanes=2, prompt_len=None, trace=None, chips=1):
    return Run(lanes=lanes, chips=chips, shapes=SHAPES,
               peaks=chip_peaks("TPU v5 lite"),
               window=win, prompt_len=prompt_len or {},
               setup_s=12.5, compiles_in_window=0, memory_peak_bytes=2 ** 31,
               trace=trace)


@pytest.mark.parametrize("name", ["itl_p95_ms", "itl_p95_ms.admit"])
def test_itl_p95_pools_every_gap(name):
    # request 0: 100 gaps of 10 ms; request 1: 5 gaps of 1 s
    t0 = [(1.0 + 0.01 * i, i) for i in range(101)]
    t1 = [(1.0 + 1.0 * i, i) for i in range(6)]
    win = window({0: t0, 1: t1}, t_open=0.5, t_close=10.0)
    gaps = itl_gaps(win)
    assert len(gaps) == 105
    got = read(name, run_of(win))
    assert got == pytest.approx(1e3 * np.percentile(gaps, 95))
    assert got == pytest.approx(10.0)
    # not the median of per-request tails, which would read 505 ms
    per = [np.percentile(np.diff([t for t, _ in ts]), 95) for ts in (t0, t1)]
    assert 1e3 * np.median(per) == pytest.approx(505.0)


def test_itl_keeps_only_gaps_with_both_tokens_inside():
    win = window({0: [(0.4, 0), (0.6, 1), (0.9, 2), (1.5, 3)]},
                 t_open=0.5, t_close=1.0)
    assert itl_gaps(win) == pytest.approx([0.3])


def test_ttft_is_attributed_to_the_releasing_completion():
    # two lanes: uid 2 is released by the first completion (uid 1, at 1.0),
    # uid 3 by the second (uid 0, at 1.5); uid 4's first token is outside
    times = {0: [(0.1, 0), (1.5, 5)], 1: [(0.2, 0), (1.0, 3)],
             2: [(1.2, 0), (1.8, 1)], 3: [(1.9, 0)], 4: [(2.5, 0)]}
    win = window(times, t_open=0.5, t_close=2.0,
                 finished={1: 1.0, 0: 1.5}, completion_order=[1, 0])
    assert ttfts(win, lanes=2) == pytest.approx([0.2, 0.4])
    assert read("ttft_p90_ms", run_of(win)) == pytest.approx(
        1e3 * np.percentile([0.2, 0.4], 90))


def test_ttft_skips_the_first_wave():
    times = {0: [(0.6, 0)], 1: [(0.7, 0)]}
    win = window(times, t_open=0.5, t_close=2.0)
    assert ttfts(win, lanes=2) == []
    assert read("ttft_p90_ms", run_of(win)) is None


def test_tokens_per_s_counts_every_received_token():
    units = [Unit("step", 1.5, [(0, 4), (1, 7)]), Unit("admit", 2.0, [(2, 0)]),
             Unit("step", 3.0, [(0, 5), (2, 1)])]
    win = window({}, t_open=1.0, t_close=3.0, units=units)
    assert read("tokens_per_s", run_of(win)) == pytest.approx(5 / 2.0)
    assert read("setup_s", run_of(win)) == 12.5


def test_lane_occupancy_from_engine_counters():
    win = window({}, t_open=0, t_close=1, steps=(10, 30),
                 occupancy=(100, 170))
    assert read("lane_occupancy", run_of(win, lanes=4)) == pytest.approx(
        100.0 * 70 / (20 * 4))


def test_step_mfu_counts_decode_and_prefill_tokens():
    units = [Unit("step", 1.5, [(0, 4), (1, 7)]), Unit("admit", 2.0, [(2, 0)])]
    win = window({}, t_open=1.0, t_close=3.0, units=units)
    run = run_of(win, prompt_len={0: 40, 1: 50, 2: 30})
    want = (decode_token_flops(SHAPES, 44) + decode_token_flops(SHAPES, 57)
            + prefill_flops(SHAPES, 30))
    assert read("step_mfu", run) == pytest.approx(100 * want / (2.0 * 197e12))


def test_step_mfu_divides_by_every_chip_of_the_cell():
    units = [Unit("step", 1.5, [(0, 4), (1, 7)]), Unit("admit", 2.0, [(2, 0)])]
    win = window({}, t_open=1.0, t_close=3.0, units=units)
    prompts = {0: 40, 1: 50, 2: 30}
    one = read("step_mfu", run_of(win, prompt_len=prompts))
    four = read("step_mfu", run_of(win, prompt_len=prompts, chips=4))
    assert four == pytest.approx(one / 4)


def test_trace_readers_are_silent_without_a_trace():
    win = window({}, t_open=0, t_close=1)
    for name in ("decode_step_ms", "aqua_decode_roofline",
                 "aqua_prefill_roofline", "device_idle_share",
                 "collective_ms", "collective_exposed_ms"):
        assert read(name, run_of(win)) is None
    assert read("peak_hbm_gib", run_of(win)) == 2.0
    assert read("compiles_in_window", run_of(win)) == 0.0


class FakeDevice:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_the_fullest_device_is_the_one_reported():
    devs = [FakeDevice({"peak_bytes_in_use": n})
            for n in (3 << 30, 7 << 30, 5 << 30, 6 << 30)]
    peaks = device_peaks(devs)
    assert peaks == [3 << 30, 7 << 30, 5 << 30, 6 << 30]
    assert fullest(peaks) == 7 << 30
    assert fullest([None, 2 << 30]) == 2 << 30
    assert fullest(device_peaks([FakeDevice(None)])) is None
    run = run_of(window({}, t_open=0, t_close=1))
    run.memory_peak_bytes = fullest(peaks)
    assert read("peak_hbm_gib", run) == 7.0
