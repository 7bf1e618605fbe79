"""The trace reduction on small recorded traces."""
import json
import os

import numpy as np
import pytest

from perfbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def test_busy_idle_kernels_and_gap_attribution():
    # window 0..100 ms; device busy 10-30 (two overlapping ops) and
    # 60-70; host spans: policy 30-60 holds the middle gap
    dev = [("fusion.1", 10 * MS, 25 * MS),
           ("run.1 [tpu_custom_call]", 20 * MS, 30 * MS),
           ("run.1 [tpu_custom_call]", 60 * MS, 70 * MS),
           ("copy.3", 150 * MS, 160 * MS)]           # outside the window
    host = [(trace.WINDOW, 0, 100 * MS),
            ("detector.update", 0, 30 * MS),
            ("policy.decide", 30 * MS, 60 * MS)]
    s = trace.summarize(dev, host, {"fleet_score": "[tpu_custom_call]"})
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.030)
    assert s["kernel_s"]["fleet_score"] == pytest.approx(0.020)
    assert s["kernel_events"]["fleet_score"] == 2
    gaps = dict(s["idle_gaps"])
    assert gaps["detector.update"] == pytest.approx(0.010)
    assert gaps["policy.decide"] == pytest.approx(0.030)
    assert gaps["host:other"] == pytest.approx(0.030)
    assert trace.idle_share_percent(s) == pytest.approx(70.0)
    assert dict(s["device_ops"])["run.1 [tpu_custom_call]"] == \
        pytest.approx(0.02)


def test_no_window_or_no_device_work_reads_nothing():
    assert trace.summarize([("a", 0, 10)], []) is None
    assert trace.summarize([], [(trace.WINDOW, 0, 10)]) is None
    assert trace.idle_share_percent(None) is None


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e (a few frames of fleet detection),
    saved as events; the reduction agrees with a timeline rasterised at
    one microsecond."""
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        rec = json.load(f)
    dev = [(trace.op_name(n), a, b) for n, a, b in rec["device"]]
    host = [tuple(e) for e in rec["host"]]
    assert dev[0][0] == "run.1 [tpu_custom_call]"
    s = trace.summarize(dev, host, {"fleet_score": "[tpu_custom_call]"})
    w0, w1 = [(a, b) for n, a, b in host if n == trace.WINDOW][0]
    us = 1000
    grid = np.zeros((w1 - w0) // us + 1, bool)
    kern = 0
    for n, a, b in dev:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            grid[(a - w0) // us:(b - w0) // us] = True
            kern += (b - a) * ("[tpu_custom_call]" in n)
    n_ops = len(dev)
    assert s["busy_s"] == pytest.approx(grid.sum() * 1e-6,
                                        abs=2 * n_ops * 1e-6)
    assert s["kernel_s"]["fleet_score"] == pytest.approx(kern / 1e9)
    assert s["kernel_events"]["fleet_score"] == rec["frames"]
    assert 0 < s["busy_s"] < s["window_s"]
