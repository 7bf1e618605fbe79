"""The program's own spans in the trace reduction, and the readers of the
per-layer metrics that read them, on tiny runs of the two drivers with
the profiler on (CPU: no device plane, so no trace summary)."""
import sys
import time

import pytest

import repro
from perfbench import trace
from perfbench.drivers import fleet_detect, guarded_train
from perfbench.run import read_layer_metric
from perfbench.spans import Spans
from perfbench.tests import tiny
from repro import tracing

MS = 1_000_000
SEED = 2**31 + 11
FLEET = ("fleet_score_call_ms", "detector_host_ms", "fleet_score_host_mb")
TRAIN = ("train_dispatch_ms", "train_host_ms")


def test_idle_goes_to_the_innermost_program_span():
    # one frame in 0..10 ms: the benchmark's detector.update holds the
    # program's spans; device work at 2-3, 4-5 (the kernel), 6-7, 8-8.5
    dev = [("fusion.1", 2 * MS, 3 * MS),
           ("fleet_score.1 [tpu_custom_call]", 4 * MS, 5 * MS),
           ("fusion.2", 6 * MS, 7 * MS),
           ("fusion.3", 8 * MS, 17 * MS // 2)]
    host = [(trace.WINDOW, 0, 10 * MS),
            ("detector.update", 0, 19 * MS // 2),
            ("detector.push", 0, 2 * MS),
            ("detector.score", 2 * MS, 6 * MS),
            ("fleet_score.call", 3 * MS, 6 * MS),
            ("fleet_score.dispatch", 3 * MS, 4 * MS),
            ("fleet_score.fetch", 4 * MS, 6 * MS),
            ("detector.verdicts", 6 * MS, 8 * MS),
            ("policy.decide", 19 * MS // 2, 10 * MS)]
    s = trace.summarize(dev, host, {"fleet_score": "[tpu_custom_call]"})
    assert s["kernel_s"]["fleet_score"] == pytest.approx(0.001)
    # each gap goes to the innermost span holding its midpoint; the last
    # (8.5-10, midpoint 9.25) lies in detector.update outside them all
    assert dict(s["idle_gaps"]) == pytest.approx({
        "detector.push": 0.002, "fleet_score.dispatch": 0.001,
        "fleet_score.fetch": 0.001, "detector.verdicts": 0.001,
        "detector.update": 0.0015})


def test_program_names_stay_apart_from_the_benchmarks():
    outside = set(fleet_detect.HOST_SPANS) | set(guarded_train.HOST_SPANS)
    assert not outside & set(tracing.SPAN_NAMES)
    assert trace.WINDOW not in tracing.SPAN_NAMES


def _fleet_run(**over):
    cfg = tiny.fleet_config(**over)
    cfg["detector"]["scorer"] = "jax"
    spans = Spans()
    res = fleet_detect.run({"name": "f", "chips": 1}, cfg,
                           tiny.mix("sparse"), SEED, 0.5, True,
                           time.perf_counter(), spans)
    return cfg, res, spans


def test_fleet_readers_read_the_traced_window():
    cfg, res, spans = _fleet_run()
    got = {m: read_layer_metric(m, res, cfg, "cpu") for m in FLEET}
    rows = cfg["hosts"] * cfg["accelerators_per_host"]
    m = len(cfg["metrics"])
    # one new ring row a frame: (1, M, N) in, (1, M, N) + 2 x (1, 1, N) out
    assert got["fleet_score_host_mb"] == pytest.approx(
        4 * rows * (2 * m + 2) / 1e6)
    frames = res["layer"]["frames"]
    inside = got["detector_host_ms"] + got["fleet_score_call_ms"]
    assert 0 < got["fleet_score_call_ms"] < inside
    assert inside * frames <= 1e3 * spans.total("detector.update")


def test_train_readers_read_the_traced_window():
    cfg = tiny.train_config()
    res = guarded_train.run({"name": "t", "chips": 1}, cfg,
                            tiny.mix("steady"), SEED, 1.0, True,
                            time.perf_counter(), Spans())
    got = {m: read_layer_metric(m, res, cfg, "cpu") for m in TRAIN}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert tracing.profiled.calls("train.dispatch") == \
        res["counters"]["window_steps_completed"]


def test_readers_read_nothing_from_a_program_without_tracing(monkeypatch):
    cfg, res, _ = _fleet_run()
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    monkeypatch.delattr(repro, "tracing")
    for m in FLEET + TRAIN:
        assert read_layer_metric(m, res, cfg, "cpu") is None
