"""The reduction from a profiler trace to busy and idle time, op sums and
idle gaps named by the benchmark's host spans."""
import gzip
import json
import os

import pytest

from benchtest import BENCH
from bench.lib import trace

FIXTURES = os.path.join(BENCH, "tests", "fixtures")


def _synthetic():
    # window 0..100; device 0 busy 10-30 and 20-40 (overlap) and 90-110
    # (clipped at 100); device 1 busy 0-50.  Spans: dispatch 40-60,
    # wait 60-95 (inner), so device 0's gaps 0-10, 40-90 fall in "none"
    # and in dispatch/wait by their middles.
    return {
        "devices": {
            "/device:TPU:0": [["fusion.1 fusion", 10, 20],
                              ["fusion.2 fusion", 20, 20],
                              ["collective-permute-done cp", 90, 20]],
            "/device:TPU:1": [["fusion.1 fusion", 0, 50]],
        },
        "host": [["bench.window", 0, 100], ["bench.step_dispatch", 40, 20],
                 ["bench.wait_previous_step", 60, 35]],
    }


def test_busy_is_the_union_of_op_intervals_in_the_window():
    s = trace.reduce(_synthetic())
    assert s["window_s"] == pytest.approx(100e-9)
    # device 0: 10-40 and 90-100 = 40; device 1: 0-50 = 50
    assert s["busy_s"] == pytest.approx(45e-9)
    assert s["devices"] == 2


def test_op_seconds_sum_inside_the_window():
    s = trace.reduce(_synthetic())
    assert s["op_s"]["fusion.1 fusion"] == pytest.approx(70e-9)
    assert s["op_s"]["collective-permute-done cp"] == pytest.approx(10e-9)
    assert trace.op_seconds(s, r"fusion") == pytest.approx(90e-9 / 2)
    assert trace.op_seconds(s, r"no-such-op") is None
    names = [n for n, _ in s["device_ops"]]
    assert names[0] == "fusion.1 fusion"


def test_gaps_are_named_by_the_innermost_host_span():
    s = trace.reduce(_synthetic())
    gaps = {(w, round(g * 1e9)) for w, g in s["idle_gaps"]}
    # device 0: 0-10 (middle 5: no span), 40-90 (middle 65: wait);
    # device 1: 50-100 (middle 75: wait)
    assert gaps == {("no bench span", 10), ("bench.wait_previous_step", 50)}
    assert [round(g * 1e9) for _, g in s["idle_gaps"]] == [50, 50, 10]


def test_a_trace_without_window_or_device_is_refused():
    raw = _synthetic()
    with pytest.raises(ValueError):
        trace.reduce({"devices": raw["devices"], "host": []})
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": raw["host"]})


@pytest.mark.parametrize("text,want", [
    ("%fusion.3 = bf16[]{:T(256)} fusion(bf16[8,8]{1,0:T(8,128)(2,1)} %x), "
     "kind=kOutput", "fusion.3 fusion"),
    ("%copy-start = (bf16[2,2]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) "
     "copy-start(bf16[2,2]{1,0} %x.1)", "copy-start copy-start"),
    ("%collective-permute-start.1 = (f32[1,8]{1,0}, f32[1,8]{1,0}) "
     "collective-permute-start(f32[1,8]{1,0} %p)",
     "collective-permute-start.1 collective-permute-start"),
    ("not an instruction", "not an instruction"),
])
def test_label(text, want):
    assert trace.label(text) == want


def test_recorded_chip_trace():
    """A few steps recorded on a TPU v5e: the reduction gives a busy
    share inside (0, 1] and names every gap by a span or by none."""
    path = os.path.join(FIXTURES, "v5e_train_1node.json.gz")
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    s = trace.reduce(raw)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["device_ops"] and s["idle_gaps"]
    spans = {n for n, _, _ in raw["host"]} | {"no bench span"}
    assert all(w in spans for w, _ in s["idle_gaps"])
    assert all(0 < v <= s["window_s"] for _, v in s["device_ops"])


def test_loops_count_as_busy_but_not_as_ops():
    raw = {"devices": {"/device:TPU:0": [["while.1 while", 0, 80],
                                         ["fusion.2 fusion", 10, 30]]},
           "host": [["bench.window", 0, 100]]}
    s = trace.reduce(raw)
    assert s["busy_s"] == pytest.approx(80e-9)
    assert s["op_s"] == {"fusion.2 fusion": pytest.approx(30e-9)}
