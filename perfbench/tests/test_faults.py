"""Runs of the harness without its look for a chip, at a size a test run
can hold, with the timed path broken underneath: ``correct`` comes out
false for each fault the cell can have, and true for a sound run.

The cells run on one chip, so there is no exchange between chips to
leave out.
"""
import time

import jax
import numpy as np
import pytest

import repro.core.detector as detector
import repro.core.policy as policy
from repro.train import trainer as trainer_mod
from perfbench.drivers import fleet_detect, guarded_train
from perfbench.run import judge
from perfbench.spans import Spans
from perfbench.tests import tiny

SEED = 2**31 + 5


def run_train(mix="failslow"):
    cfg = tiny.train_config()
    res = guarded_train.run({"name": "t", "chips": 1}, cfg, tiny.mix(mix),
                            SEED, 1.5, False, time.perf_counter(), Spans())
    return judge(cfg, res)


def run_fleet(mix="sparse"):
    cfg = tiny.fleet_config()
    res = fleet_detect.run({"name": "f", "chips": 1}, cfg, tiny.mix(mix),
                           SEED, 0.5, False, time.perf_counter(), Spans())
    return judge(cfg, res)


def _broken_step(monkeypatch, change):
    """Replace the trainer's compiled step by ``change`` of the real one."""
    def build(self, p_sh, o_sh):
        step = trainer_mod.make_train_step(self.model, self.cfg.opt,
                                           self.cfg.microbatch)
        return jax.jit(lambda p, o, b: change(step, p, o, b))
    monkeypatch.setattr(trainer_mod.Trainer, "_build_step", build)


def test_sound_runs_are_correct():
    assert run_train()[0]
    assert run_fleet("storm")[0]


def test_step_returns_its_state_unchanged(monkeypatch):
    _broken_step(monkeypatch, lambda step, p, o, b: (p, o, step(p, o, b)[2]))
    ok, checks = run_train("steady")
    assert not ok and checks["grad_leaf_gap"]["value"] > 0.9


def test_half_the_batch_left_out(monkeypatch):
    _broken_step(monkeypatch, lambda step, p, o, b: step(
        p, o, {k: v[:v.shape[0] // 2] for k, v in b.items()}))
    ok, checks = run_train("steady")
    assert not ok, checks


def test_a_loss_altered_where_it_is_produced(monkeypatch):
    def altered(step, p, o, b):
        p, o, m = step(p, o, b)
        return p, o, {**m, "loss": m["loss"] * 1.01}
    _broken_step(monkeypatch, altered)
    ok, checks = run_train("steady")
    assert not ok and checks["loss_rel"]["value"] > 0.005


def _flip_first_verdict(monkeypatch):
    update = detector.StragglerDetector.update

    def flipped(self, frame):
        fa = update(self, frame)
        fa.flagged[0] = ~fa.flagged[0]
        return fa
    monkeypatch.setattr(detector.StragglerDetector, "update", flipped)


def test_a_guard_verdict_altered_in_the_job(monkeypatch):
    _flip_first_verdict(monkeypatch)
    ok, checks = run_train()
    assert not ok and checks["verdict_mismatch"]["value"] > 0


def test_a_fleet_verdict_altered(monkeypatch):
    _flip_first_verdict(monkeypatch)
    ok, checks = run_fleet()
    assert not ok and checks["verdict_mismatch"]["value"] > 0


@pytest.mark.parametrize("mix", ["sparse", "storm"])
def test_a_fleet_decision_altered(monkeypatch, mix):
    decide = policy.TieredPolicy.decide

    def dropped(self, fa):
        out = decide(self, fa)
        if out:
            out[-1] = policy.Decision(out[-1].node_id,
                                      policy.Action.NONE, "", 0.0)
        return out
    monkeypatch.setattr(policy.TieredPolicy, "decide", dropped)
    ok, checks = run_fleet(mix)
    assert not ok and checks["decision_mismatch"]["value"] > 0
    assert np.isfinite(checks["slowdown_ulps"]["value"])
