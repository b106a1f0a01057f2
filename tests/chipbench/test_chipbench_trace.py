"""The trace reduction on event lists small enough to work by hand, and on
an event list cut from one chip trace (trace_events_sample.json)."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import trace as t  # noqa: E402

SAMPLE = Path(__file__).with_name("trace_events_sample.json")

# one op line, microsecond-sized numbers: a `while` spanning two kernels and
# a permute, then a fusion after a gap
LINE = [
    ["while.1", 100, 500],                 # 100..600
    ["burst_flash_fwd", 120, 200],         # 120..320
    ["collective-permute-done.3", 330, 50],  # 330..380
    ["burst_flash_bwd_rect", 400, 180],    # 400..580
    ["fusion.7", 700, 100],                # 700..800
]
RAW = {
    "devices": {"/device:TPU:0": [LINE]},
    "spans": [["bench.window", 0, 1000], ["bench.dispatch", 0, 110],
              ["bench.block", 110, 690], ["bench.next_batch", 900, 100]],
}


def test_self_segments_charge_a_parent_only_what_no_child_covers():
    segs = t.self_segments(LINE)
    by_name = {}
    for name, start, end in segs:
        by_name[name] = by_name.get(name, 0) + end - start
    # the while keeps 20 + 10 + 20 + 20 of its 500
    assert by_name == {"while.1": 70, "burst_flash_fwd": 200,
                       "collective-permute-done.3": 50,
                       "burst_flash_bwd_rect": 180, "fusion.7": 100}
    # disjoint and in order
    assert all(a[2] <= b[1] for a, b in zip(segs, segs[1:]))


def test_interval_arithmetic():
    assert t.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert t.total(t.union([(0, 2), (1, 3)])) == 3
    assert t.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == \
        [(0, 2), (3, 5), (7, 9)]


def test_reduction_by_hand():
    trace = t.reduce_trace(RAW, steps=1)
    assert t.window_seconds(trace) == pytest.approx(1000e-9)
    # busy: 100..600 and 700..800
    assert t.busy_seconds(trace) == pytest.approx(600e-9)
    segs = trace["devices"]["/device:TPU:0"]
    assert t.seconds_where(segs, t.is_flash) == pytest.approx(380e-9)
    # on one line nothing overlaps the permute: all of it is exposed
    assert t.permute_seconds(trace) == pytest.approx((50e-9, 50e-9))
    # idle: 0..100 (dispatch), 600..700 (block), 800..900 (no span),
    # 900..1000 (next_batch)
    gaps = t.idle_gaps_by_span(trace)
    assert gaps == pytest.approx({"bench.dispatch": 100e-9,
                                  "bench.block": 100e-9,
                                  "bench.next_batch": 100e-9,
                                  "(no span)": 100e-9})
    b = t.breakdown(trace)
    assert b["device_ops"][0] == ["burst_flash_fwd", pytest.approx(200e-9)]
    assert len(b["idle_gaps"]) == 4


def test_exposed_permute_is_what_no_compute_covers():
    # the core: a kernel 0..100, the permute's -done waiting 100..130, a
    # fusion 130..200; in flight beside it: the transfer 50..130
    raw = {"devices": {"/device:TPU:0": [[
        ["burst_flash_fwd", 0, 100], ["collective-permute-done.1", 100, 30],
        ["fusion.2", 130, 70]]]},
        "async": {"/device:TPU:0": [[["collective-permute.1", 50, 80]]]},
        "spans": [["bench.window", 0, 200]]}
    trace = t.reduce_trace(raw, 1)
    # 80 in flight, of which the 30 the core waited are exposed
    assert t.permute_seconds(trace) == pytest.approx((80e-9, 30e-9))
    # the transfer is no op of the core: busy is the core's line alone
    assert t.busy_seconds(trace) == pytest.approx(200e-9)
    assert "collective-permute.1" not in t.seconds_by_name(trace)
    # the profiler writes the transfers of the first chip only: a chip
    # without them does not dilute the mean
    raw["devices"]["/device:TPU:1"] = raw["devices"]["/device:TPU:0"]
    raw["async"]["/device:TPU:1"] = [[]]
    assert t.permute_seconds(t.reduce_trace(raw, 1)) == pytest.approx(
        (80e-9, 30e-9))


def test_events_are_cut_to_the_window_and_chips_are_averaged():
    raw = {"devices": {"/device:TPU:0": [[["fusion.1", 0, 300]]],
                       "/device:TPU:1": [[["fusion.1", 150, 100]]]},
           "spans": [["bench.window", 100, 200]]}  # 100..300
    trace = t.reduce_trace(raw, 1)
    # chip 0 busy 200 of 200, chip 1 busy 100 of 200
    assert t.busy_seconds(trace) == pytest.approx(150e-9)
    assert t.seconds_by_name(trace) == pytest.approx({"fusion.1": 150e-9})


def test_a_trace_without_its_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        t.reduce_trace({"devices": {}, "spans": []}, 1)


# -- the recorded step ------------------------------------------------------

@pytest.fixture(scope="module")
def sample():
    raw = json.loads(SAMPLE.read_text())
    return raw, t.reduce_trace(raw, steps=1)


def _raster(intervals, t1, shift=0, cell=1000):
    """Which microsecond cells of [0, t1) the intervals cover: the slow and
    obvious way, against which the interval arithmetic is held."""
    import numpy as np

    covered = np.zeros(int(t1) // cell + 1, bool)
    for start, end in intervals:
        covered[int(start + shift) // cell:int(end + shift) // cell] = True
    return covered


def test_recorded_step_device_clock_is_shifted_onto_the_hosts(sample):
    raw, trace = sample
    # the profiler wrote the device's last op 2.56 ms before the host's
    # block returned; shifted, it ends exactly there, and no op starts
    # before the dispatch that launched it
    assert trace["device_clock_shift"] == 2_557_881
    spans = {n: (s, e) for n, s, e in trace["spans"]}
    segs = trace["devices"]["/device:TPU:0"]
    assert max(e for _, _, e in segs) == spans["bench.block"][1]
    assert min(s for _, s, _ in segs) > spans["bench.dispatch"][0]


def test_recorded_step_matches_a_rasterised_timeline(sample):
    raw, trace = sample
    t1 = raw["spans"][0][2]
    shift = trace["device_clock_shift"]
    events = raw["devices"]["/device:TPU:0"][0]
    busy = _raster([(s, s + d) for _, s, d in events], t1, shift)
    flash = _raster([(s, s + d) for n, s, d in events if t.is_flash(n)],
                    t1, shift)
    segs = trace["devices"]["/device:TPU:0"]
    # a cell of 1 us per event edge is the raster's error: under 0.7 ms here
    assert t.busy_seconds(trace) == pytest.approx(busy.sum() * 1e-6, abs=7e-4)
    assert t.seconds_where(segs, t.is_flash) == pytest.approx(
        flash.sum() * 1e-6, abs=1e-4)
    gaps = t.idle_gaps_by_span(trace)
    for name, start, end in trace["spans"]:
        in_span = _raster([(start, end)], t1)
        assert gaps[name] == pytest.approx((in_span & ~busy).sum() * 1e-6,
                                           abs=2e-4)
    assert sum(gaps.values()) == pytest.approx(
        t.window_seconds(trace) - t.busy_seconds(trace))


def test_recorded_step_reads_what_the_chip_run_reported(sample):
    """The numbers of this step as the chip run's own reduction gave them
    (PERF.md, PR 24: flash 79.19 and XLA 418.01 ms a step over five)."""
    _, trace = sample
    segs = trace["devices"]["/device:TPU:0"]
    assert t.window_seconds(trace) == pytest.approx(0.500548667)
    assert t.busy_seconds(trace) == pytest.approx(0.497159761)
    assert t.seconds_where(segs, t.is_flash) == pytest.approx(0.079188879)
    other = lambda n: not (t.is_flash(n) or t.is_collective(n))
    assert t.seconds_where(segs, other) == pytest.approx(0.417970882)
    assert t.permute_seconds(trace) == (0, 0)  # one chip, no ring
    by_name = t.seconds_by_name(trace)
    # four layers: the forward twice under remat, and the rectangular
    # backward (group 4: no triangular kernel), each under ONE stable name
    assert by_name["burst_flash_fwd"] == pytest.approx(0.038110126)
    assert by_name["burst_flash_bwd_rect"] == pytest.approx(0.041078753)
    top = t.breakdown(trace)
    assert [n for n, _ in top["device_ops"][:2]] == [
        "burst_flash_bwd_rect", "burst_flash_fwd"]
    assert len(top["device_ops"]) == 10
    assert dict(top["idle_gaps"]) == pytest.approx({
        "bench.next_batch": 0.00135821, "bench.dispatch": 0.00112662,
        "bench.block": 0.000710676, "(no span)": 0.0001934})
