"""The trace reduction: busy union, idle share and gap attribution."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

from bench import trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "cpu_window.xplane.pb"


def test_union_merges_and_clips():
    ivs = [(5, 8, "a"), (0, 3, "b"), (2, 4, "c"), (9, 20, "d")]
    assert trace.union(ivs, 1, 12) == [[1, 4], [5, 8], [9, 12]]


def test_gaps_cover_the_rest_of_the_window():
    busy = trace.union([(2, 4, "a"), (6, 7, "b")], 0, 10)
    assert trace.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]


def test_innermost_span_names_a_gap():
    spans = [(0, 100, "bench.window"), (10, 40, "bench.call"),
             (20, 30, "PjitFunction(f)")]
    assert trace.innermost(spans, [35, 25, 200, 5]) == [
        "bench.call", "PjitFunction(f)", trace.NO_SPAN, "bench.window"]


def test_reduce_on_intervals():
    devices = {"d0": [(10, 30, "op_a"), (20, 40, "op_b"), (60, 70, "op_a")]}
    spans = [(40, 60, "bench.host")]
    red = trace.reduce(devices, spans, 0, 100)
    assert abs(red["busy_s"] - 40e-9) < 1e-15
    assert abs(red["window_s"] - 100e-9) < 1e-15
    ops = dict(red["breakdown"]["device_ops"])
    assert abs(ops["op_a"] - 30e-9) < 1e-15
    idle = dict(red["breakdown"]["idle_gaps"])
    assert abs(idle["bench.host"] - 20e-9) < 1e-15
    assert abs(idle[trace.NO_SPAN] - 40e-9) < 1e-15


def test_recorded_cpu_trace():
    """Two sorts of 2^18 keys with a 50 ms host span between them: the
    device is busy less than the window, and the longest idle stretch is
    the host span's."""
    red = trace.reduce_file(str(DATA), "cpu", "bench.window")
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["window_s"] > 0.05
    ops = dict(red["breakdown"]["device_ops"])
    assert "sort.0" in ops
    top_gap, seconds = red["breakdown"]["idle_gaps"][0]
    assert top_gap == "bench.host_work" and seconds >= 0.05
    assert abs(sum(s for _, s in red["breakdown"]["idle_gaps"])
               - (red["window_s"] - red["busy_s"])) < 1e-6
