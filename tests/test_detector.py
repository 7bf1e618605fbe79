"""Unit tests for the peer-relative straggler detector (§4.2)."""
import numpy as np
import pytest

from repro.core import (Action, DetectorConfig, OnlineMonitor, PolicyConfig,
                        StragglerDetector, TieredPolicy, robust_z)
from repro.core.telemetry import Frame


def mk_frame(step, step_times, temps=None, n=None, valid=None):
    n = n or len(step_times)
    metrics = {
        "step_time": np.asarray(step_times, float),
        "gpu_temp": np.asarray(temps if temps is not None
                               else np.full(n, 58.0), float),
        "gpu_util": np.full(n, 0.97),
        "gpu_freq": np.full(n, 1.93),
        "gpu_power": np.full(n, 350.0),
        "nic_errors": np.zeros(n),
        "nic_tx_rate": np.full(n, 50.0),
        "nic_up": np.ones(n),
    }
    return Frame(t=float(step * 60), step=step,
                 node_ids=np.arange(n, dtype=np.int64), metrics=metrics,
                 valid=np.ones(n, bool) if valid is None else valid)


def feed(det, times_fn, windows, n=16):
    out = []
    for w in range(windows):
        out = det.update(mk_frame(w, times_fn(w)))
    return out


class TestRobustZ:
    def test_outlier_scores_high(self):
        v = np.array([10.0] * 15 + [13.0])
        z = robust_z(v)
        assert z[-1] > 10
        assert np.all(np.abs(z[:-1]) < 3), z[:-1]

    def test_symmetric_noise_scores_low(self):
        rng = np.random.RandomState(0)
        v = 10 + rng.normal(0, 0.1, 64)
        assert np.max(np.abs(robust_z(v))) < 6


class TestDetector:
    def test_no_flags_on_healthy_fleet(self):
        det = StragglerDetector()
        rng = np.random.RandomState(1)
        res = feed(det, lambda w: 10 + rng.normal(0, 0.1, 16), 12)
        assert not any(a.flagged for a in res)

    def test_sustained_straggler_flagged(self):
        det = StragglerDetector()
        times = lambda w: [10.0] * 15 + [12.0]
        res = feed(det, times, 6)
        by = {a.node_id: a for a in res}
        assert by[15].flagged
        assert by[15].step_deviant
        assert 0.15 < by[15].slowdown < 0.25
        assert not any(a.flagged for a in res if a.node_id != 15)

    @pytest.mark.parametrize("window,persistence", [(6, 3), (300, 260)])
    def test_sustained_straggler_counted_over_the_window(self, window,
                                                         persistence):
        """Deviation counts hold the whole window: at 260 deviant windows
        a uint8 count would wrap to 4 and drop the straggler."""
        det = StragglerDetector(DetectorConfig(
            window=window, persistence=persistence, scorer="numpy"))
        times = lambda w: [10.0] * 15 + [12.0]
        res = feed(det, times, persistence - 1)
        assert not res[15].step_deviant
        res = det.update(mk_frame(persistence, times(persistence)))
        assert res[15].flagged and res[15].step_deviant
        excess = np.float32(12.0) / np.float32(10.0) - np.float32(1.0)
        assert res[15].slowdown == pytest.approx(float(excess), rel=1e-4)
        assert not any(a.flagged for a in res if a.node_id != 15)

    def test_transient_spike_not_flagged(self):
        det = StragglerDetector(DetectorConfig(persistence=3))
        # node 7 spikes for only 2 of 8 windows
        def times(w):
            t = [10.0] * 16
            if w in (3, 4):
                t[7] = 14.0
            return t
        res = feed(det, times, 8)
        assert not any(a.flagged for a in res)

    def test_needs_full_window_before_flagging(self):
        det = StragglerDetector(DetectorConfig(persistence=4))
        res = feed(det, lambda w: [10.0] * 15 + [13.0], 2)
        assert not any(a.step_deviant for a in res)

    def test_stall_flagged_immediately(self):
        det = StragglerDetector()
        f = mk_frame(0, [10.0] * 15 + [100.0])
        res = det.update(f)
        assert res[15].stalled and res[15].flagged

    def test_missing_heartbeat_is_stall(self):
        det = StragglerDetector()
        valid = np.ones(16, bool)
        valid[3] = False
        res = det.update(mk_frame(0, [10.0] * 16, valid=valid))
        assert res[3].stalled

    def test_hysteresis_clears_after_clean_windows(self):
        det = StragglerDetector(DetectorConfig(clear_windows=3))
        feed(det, lambda w: [10.0] * 15 + [12.5], 6)
        res = feed(det, lambda w: [10.0] * 16, 3)
        assert {a.node_id: a.flagged for a in res}[15]   # still latched
        # the stale deviant windows must age out of the history (window=6)
        # AND clear_windows clean evaluations must accumulate
        res = feed(det, lambda w: [10.0] * 16, 6)
        assert not {a.node_id: a.flagged for a in res}[15]

    def test_hardware_only_flag_needs_multiple_signals(self):
        det = StragglerDetector(DetectorConfig(min_support=2))
        # only temperature deviates -> no flag
        for w in range(8):
            f = mk_frame(w, [10.0] * 16,
                         temps=[58.0] * 10 + [80.0] + [58.0] * 5)
            res = det.update(f)
        assert not res[10].flagged
        assert res[10].support == ["gpu_temp"]

    def test_membership_change_resets_history(self):
        det = StragglerDetector()
        feed(det, lambda w: [10.0] * 15 + [12.5], 6, n=16)
        det.update(mk_frame(99, [10.0] * 12))    # 12-node fleet now
        assert len(det.history) == 1

    def test_replacement_does_not_inherit_history(self):
        """A swapped-in spare must not be flagged off its predecessor's
        slow history column (regression: replacement cascade)."""
        det = StragglerDetector()
        # node 15 is slow for 6 windows, then gets replaced by node 99
        feed(det, lambda w: [10.0] * 15 + [13.0], 6)
        f = mk_frame(10, [10.0] * 16)
        f.node_ids = np.array(list(range(15)) + [99], dtype=np.int64)
        res = det.update(f)
        by = {a.node_id: a for a in res}
        assert not by[99].step_deviant
        assert not by[99].flagged


class TestPolicy:
    def _assess(self, slowdown, stalled=False, support=()):
        from repro.core.detector import NodeAssessment
        return NodeAssessment(0, slowdown, stalled, list(support),
                              slowdown > 0, True)

    def test_tiers(self):
        pol = TieredPolicy(PolicyConfig())
        assert pol.decide([self._assess(0.25)])[0].action == \
            Action.IMMEDIATE_RESTART
        assert pol.decide([self._assess(0.12)])[0].action == \
            Action.DEFER_TO_CHECKPOINT
        assert pol.decide([self._assess(0.0, support=["gpu_temp",
                                                      "gpu_freq"])])[0] \
            .action == Action.PENDING_VERIFICATION
        assert pol.decide([self._assess(0.0, stalled=True)])[0].action == \
            Action.IMMEDIATE_RESTART

    def test_unflagged_ignored(self):
        from repro.core.detector import NodeAssessment
        pol = TieredPolicy()
        a = NodeAssessment(0, 0.5, False, [], True, flagged=False)
        assert pol.decide([a]) == []


class TestMonitor:
    def test_pending_emitted_once(self):
        mon = OnlineMonitor(DetectorConfig(persistence=3, min_support=2))
        events = []
        for w in range(10):
            f = mk_frame(w, [10.0] * 16)
            f.metrics["gpu_temp"][5] = 85.0
            f.metrics["gpu_freq"][5] = 1.3
            events += mon.observe(f)
        pends = [e for e in events
                 if e.decision.action == Action.PENDING_VERIFICATION]
        assert len(pends) == 1 and pends[0].decision.node_id == 5
