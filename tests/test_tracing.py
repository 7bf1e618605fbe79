"""repro.tracing: spans and counters off, attached and under a profiler
trace, the names the program emits, and what each instrumented layer
records on the CPU."""
import ast
import gc
import os
import tempfile

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core.detector import DetectorConfig, StragglerDetector
from repro.core.telemetry import Frame
from repro.kernels.fleet_score import score_rows

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src", "repro")


@pytest.fixture(autouse=True)
def clean():
    tracing.detach()
    tracing.counters.clear()
    yield
    tracing.detach()
    tracing.counters.clear()


@pytest.fixture
def rec():
    r = tracing.Recorder()
    tracing.attach(r)
    return r


def _counts(r):
    """Calls of each span but the collections, which come when they do."""
    return {k: r.calls(k) for k in r.stats if k != "python.gc"}


def _profile():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


# ------------------------------------------------------------ the facility

def test_off_records_nothing():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    before = tracing.profiled
    seen = None if before is None else _counts(before)
    with tracing.span("detector.push"):
        with tracing.span("detector.score"):
            pass
    tracing.count("guard.windows_scored", 3)
    assert tracing.counters == {}
    assert tracing.profiled is before
    assert before is None or _counts(before) == seen


def test_attached_records_nested_spans_and_counters(rec):
    with tracing.span("train.hook"):
        with tracing.span("guard.hook"):
            with tracing.span("guard.observe"):
                pass
        with tracing.span("guard.hook"):
            pass
    tracing.count("guard.windows_scored")
    tracing.count("fleet_score.bytes_in", 4096)
    tracing.count("fleet_score.bytes_in", 4096)
    assert _counts(rec) == {"train.hook": 1, "guard.hook": 2,
                            "guard.observe": 1}
    assert rec.total("train.hook") >= rec.total("guard.hook") >= \
        rec.stats["guard.hook"][2] >= rec.total("guard.observe") > 0
    assert tracing.counters == {"guard.windows_scored": 1,
                                "fleet_score.bytes_in": 8192}
    tracing.detach()
    with tracing.span("train.hook"):
        tracing.count("guard.windows_scored")
    assert _counts(rec)["train.hook"] == 1
    assert tracing.counters["guard.windows_scored"] == 1


def test_any_recorder_of_the_same_shape():
    class Calls:
        def __init__(self):
            self.names = []

        def span(self, name):
            self.names.append(name)
            return tracing.Recorder().span(name)

    r = Calls()
    tracing.attach(r)
    with tracing.span("policy.tiers"):
        pass
    assert r.names == ["policy.tiers"]


def test_attach_clears_the_counters(rec):
    tracing.count("guard.windows_scored", 5)
    tracing.attach(tracing.Recorder())
    assert tracing.counters == {}


def test_garbage_collections_are_a_span_while_attached(rec):
    gc.collect()
    assert rec.calls("python.gc") >= 1
    assert tracing.counters["python.gc_collections"] >= 1
    tracing.detach()
    assert tracing._on_gc not in gc.callbacks
    n = rec.calls("python.gc")
    gc.collect()
    assert rec.calls("python.gc") == n


def test_a_profiler_trace_gets_its_own_record():
    firsts = []
    for _ in range(2):
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d, profiler_options=_profile())
            try:
                with tracing.span("detector.push"):
                    pass
                tracing.count("detector.rows_scored")
            finally:
                jax.profiler.stop_trace()
        firsts.append(tracing.profiled)
        with tracing.span("detector.push"):     # after the trace: off
            tracing.count("detector.rows_scored")
        assert _counts(tracing.profiled) == {"detector.push": 1}
        assert tracing.counters == {"detector.rows_scored": 1}
    assert firsts[0] is not firsts[1]


def test_an_attach_inside_a_trace_leaves_its_record_whole():
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=_profile())
        try:
            with tracing.span("detector.push"):
                pass
            mine = tracing.Recorder()
            tracing.attach(mine)
            with tracing.span("detector.score"):
                pass
            tracing.detach()
            assert tracing._on_gc in gc.callbacks
            with tracing.span("detector.push"):
                pass
        finally:
            jax.profiler.stop_trace()
    assert _counts(mine) == {"detector.score": 1}
    assert _counts(tracing.profiled) == {"detector.push": 2}
    with tracing.span("detector.push"):         # the trace is over
        pass
    assert tracing._on_gc not in gc.callbacks


# ------------------------------------------------------------------ names

def _emitted():
    """(span names, counter names) of every ``tracing.span`` /
    ``tracing.count`` call in the program; each must name a literal."""
    spans, counts = set(), set()
    for dirpath, _, files in os.walk(SRC):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn)) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Attribute) and \
                        isinstance(node.func.value, ast.Name) and \
                        node.func.value.id == "tracing" and \
                        node.func.attr in ("span", "count"):
                    arg = node.args[0]
                    assert isinstance(arg, ast.Constant) and \
                        isinstance(arg.value, str), (fn, node.lineno)
                    (spans if node.func.attr == "span" else counts).add(
                        arg.value)
    return spans, counts


def test_every_name_is_declared_once_and_emitted():
    spans, counts = _emitted()
    assert len(set(tracing.SPAN_NAMES)) == len(tracing.SPAN_NAMES)
    assert len(set(tracing.COUNTER_NAMES)) == len(tracing.COUNTER_NAMES)
    # python.gc is opened by the module itself, from gc.callbacks
    assert spans | {"python.gc"} == set(tracing.SPAN_NAMES)
    assert counts | {"python.gc_collections"} == set(tracing.COUNTER_NAMES)


# ----------------------------------------------------------------- layers

def _frame(step, ids, rng):
    n = len(ids)
    st = rng.normal(1.0, 0.01, n).astype(np.float32)
    metrics = {"step_time": st,
               "gpu_temp": rng.normal(60, 1, n).astype(np.float32),
               "gpu_util": rng.normal(0.9, 0.01, n).astype(np.float32)}
    return Frame(t=float(step), step=step, node_ids=ids, metrics=metrics,
                 valid=np.ones(n, bool))


def test_detector_phases_and_rows_scored(rec):
    rng = np.random.RandomState(0)
    ids = np.arange(64, dtype=np.int64)
    cfg = DetectorConfig(window=4)
    det = StragglerDetector(cfg)
    rows = []
    for k in range(6):
        det.update(_frame(k, ids, rng))
        rows.append(tracing.counters["detector.rows_scored"])
    # one new row a window, the fresh ring's first row included
    assert np.diff([0] + rows).tolist() == [1] * 6
    assert _counts(rec) == {"detector.push": 6, "detector.score": 6,
                            "detector.verdicts": 6}
    # a replaced node backfills its column: the whole ring is rescored
    ids2 = ids.copy()
    ids2[5] = 1000
    det.update(_frame(6, ids2, rng))
    det.update(_frame(7, ids2, rng))
    assert tracing.counters["detector.rows_scored"] == 6 + cfg.window + 1


@pytest.mark.parametrize("backend,n,padded", [
    ("jax", 200, 200), ("pallas", 200, 256), ("pallas", 256, 256)])
def test_scorer_round_trip_bytes(rec, backend, n, padded):
    r, m = 2, 3
    mats = np.random.RandomState(1).rand(r, m, n).astype(np.float32)
    score_rows(mats, (1.0, 1.0, -1.0), 0, backend=backend)
    # float32 in; float32 verdicts (R, M, N) and two (R, 1, N) rows out
    assert tracing.counters["fleet_score.bytes_in"] == 4 * r * m * padded
    assert tracing.counters["fleet_score.bytes_out"] == \
        4 * (r * m * padded + 2 * r * padded)
    assert _counts(rec) == {"fleet_score.call": 1,
                            "fleet_score.dispatch": 1,
                            "fleet_score.fetch": 1}


def test_numpy_scorer_has_no_round_trip(rec):
    mats = np.random.RandomState(2).rand(1, 2, 64).astype(np.float32)
    score_rows(mats, (1.0, 1.0), 0, backend="numpy")
    assert "fleet_score.call" not in rec.stats
    assert "fleet_score.bytes_in" not in tracing.counters


def test_guard_hook_windows(rec):
    from repro.guard import GuardStepHook
    hook = GuardStepHook(window_steps=2, warmup_windows=1, n_peers=7)
    for step in range(1, 9):
        hook(step, 0.1, {})
    # 4 windows close, the first is warm-up: 3 are scored
    assert _counts(rec)["guard.hook"] == 8
    assert _counts(rec)["guard.observe"] == 3
    assert tracing.counters["guard.windows_scored"] == 3
    assert _counts(rec)["policy.tiers"] == 3
    assert _counts(rec)["detector.push"] == 3


@pytest.fixture(scope="module")
def small():
    from repro.configs import get_config, reduced
    from repro.models.model import Model
    from repro.train import DataConfig, SyntheticLM
    cfg = reduced(get_config("qwen3-4b"))
    return Model(cfg), SyntheticLM(DataConfig(cfg.vocab_size, 32, 4))


def test_trainer_phases_once_a_step(rec, small):
    from repro.train import CheckpointManager, TrainConfig, Trainer
    model, data = small
    asked = []

    def hook(step, wall, metrics):
        if step == 6 and not asked:
            asked.append(step)
            return True
        return False

    with tempfile.TemporaryDirectory() as d:
        tr = Trainer(model, data, TrainConfig(steps=8, ckpt_interval=4),
                     ckpt=CheckpointManager(d, async_save=False), hook=hook)
        tr.run()
    # steps 1-6, the restart rewinds to 4, then 5-8: ten steps taken
    per_step = ("train.batch", "train.dispatch", "train.wait",
                "train.metrics", "train.snapshot", "train.hook")
    assert _counts(rec) == {**{s: 10 for s in per_step},
                            "train.restore": 1}


def test_fast_snapshot_split(rec, small):
    from repro.train import TieredCheckpointManager, init_opt_state
    model, _ = small
    params, _ = model.init_params(jax.random.key(0))
    opt = init_opt_state(params)
    with tempfile.TemporaryDirectory() as d:
        TieredCheckpointManager(d, async_save=False).save_fast(
            3, params, opt)
    assert _counts(rec) == {"ckpt.snapshot.copy": 1,
                            "ckpt.snapshot.write": 1}
    want = sum(np.asarray(x).nbytes for x in
               jax.tree.leaves(params) + jax.tree.leaves(opt))
    assert tracing.counters["ckpt.snapshot_bytes"] == want
