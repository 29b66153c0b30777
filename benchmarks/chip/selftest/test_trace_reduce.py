"""The trace-to-metrics reduction: on rows small enough to sum by hand, and on
a recorded chip trace (`recorded_trace.json.gz`, cut by `record_trace.py` from
a traced run of `resnet18_staged` on a TPU v5e)."""

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import trace_reduce  # noqa: E402

US = 1000


def hand_made():
    """One device, a 1000 us window. Ops: [100,300) conv, [250,400) add
    (overlaps), [600,900) dot. Busy = 300 + 300 = 600 us. Gaps: [0,100),
    [400,600), [900,1000): the loader's span covers the middle one, nothing
    the first, bench.dispatch the last."""
    return {
        "devices": [{"name": "/device:TPU:0", "ops": [
            ["fusion.1", 100 * US, 200 * US, "convolution fusion"],
            ["add.2", 250 * US, 150 * US, "non-fusion elementwise"],
            ["dot.3", 600 * US, 300 * US, ""]],
            "modules": [["jit_step(1)", 100 * US, 300 * US],
                        ["jit_step(1)", 600 * US, 300 * US],
                        ["jit_other", 950 * US, 10 * US]]}],
        "host": [["bench.window", 0, 1000 * US],
                 ["bench.loader_next", 390 * US, 200 * US],
                 ["train", 880 * US, 110 * US],
                 ["bench.dispatch", 890 * US, 100 * US]]}


def test_reduction_of_hand_made_rows():
    r = trace_reduce.reduce(hand_made(), "jit_step")
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["busy_s"] == pytest.approx(600e-6)
    assert r["idle_pct"] == pytest.approx(40.0)
    # conv 200 + dot 300 of 650 us of op time (overlap counted per op)
    assert r["mxu_share_pct"] == pytest.approx(100 * 500 / 600)
    assert r["device_step_ms"] == pytest.approx(0.3)
    assert r["device_steps"] == 2
    assert r["device_ops"][0] == ["dot.3", pytest.approx(300e-6)]
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.loader_next"] == pytest.approx(200e-6)
    assert gaps["host.unattributed"] == pytest.approx(100e-6)
    assert gaps["bench.dispatch"] == pytest.approx(100e-6)
    assert r["host_spans"]["train"] == [pytest.approx(110e-6), 1]


def test_no_device_events_reads_nothing():
    assert trace_reduce.reduce({"devices": [], "host": []})["busy_s"] is None


def test_recorded_chip_trace():
    path = os.path.join(HERE, "recorded_trace.json.gz")
    with gzip.open(path, "rt") as f:
        events = json.load(f)
    r = trace_reduce.reduce(events, "jit_step")
    ops = events["devices"][0]["ops"]
    # busy can never pass the window nor the plain sum of op durations
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] <= sum(o[2] for o in ops) / 1e9 + 1e-9
    # a staged resnet18 window: the device is all but never idle, its step
    # is ~153 ms and most of its time is in convolution fusions
    assert r["idle_pct"] < 2.0
    assert 140.0 < r["device_step_ms"] < 170.0
    assert 50.0 < r["mxu_share_pct"] < 95.0
    assert len(r["device_ops"]) == 10
    total = sum(v for _, v in r["device_ops"])
    assert total <= r["busy_s"] * 1.5
