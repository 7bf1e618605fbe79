"""Guard's decision reference against the program, on runs of the
program's own step hook driven by a trainer's restart protocol with
seeded jittered step times and spikes. The reference is fed only what
the run recorded; a count of restarts fixed in advance appears nowhere.
"""
import numpy as np
import pytest

from perfbench.drivers.guarded_train import (RecordingHook, build_hook,
                                             record_decisions)
from perfbench.references import guard_decisions
from perfbench.spans import Spans
from perfbench.tests import tiny


def drive(seed, steps, fault=None, onset=0, ckpt_every=0, snapshot=1,
          spike_p=0.02, jitter=0.02):
    """A trainer's loop around the hook: step times 0.21 s with seeded
    jitter and spikes; on a restart request, rewind to the snapshot step
    and tell the hook. Returns what the benchmark records."""
    cfg = tiny.train_config()
    hook = build_hook(cfg, seed)
    frames = record_decisions(hook)
    rhook = RecordingHook(hook, Spans(), fault, onset=onset, sleep=False)
    rng = np.random.default_rng(seed)
    step = done = 0
    while done < steps:
        step += 1
        done += 1
        wall = 0.21 * (1 + jitter * rng.standard_normal())
        if rng.random() < spike_p:
            wall *= rng.uniform(1.3, 3.0)
        if ckpt_every and step % ckpt_every == 0:
            rhook.on_checkpoint(step)
        if rhook(step, wall, {"loss": 1.0}):
            step = snapshot
            hook.on_restart(step)
    program = {"frames": frames, "restarts": rhook.restart_steps}
    ref = guard_decisions.replay(rhook.log, [f["peers"] for f in frames],
                                 cfg["guard"])
    return program, ref, rhook


ZERO = {"window_mismatch": 0, "verdict_mismatch": 0,
        "decision_mismatch": 0, "restart_mismatch": 0}


@pytest.mark.parametrize("seed", [1, 2, 3, 2**31 + 7])
def test_healthy_job_with_jitter_and_spikes(seed):
    program, ref, _ = drive(seed, 400)
    assert len(program["frames"]) > 50
    assert guard_decisions.compare(program, ref) == ZERO


@pytest.mark.parametrize("seed", [4, 5, 6])
def test_immediate_fault(seed):
    program, ref, rhook = drive(seed, 300, {"factor": 1.5}, onset=60)
    assert program["restarts"], "a 1.5x node was never swapped"
    assert guard_decisions.compare(program, ref) == ZERO
    assert rhook.hook.node_id != rhook.fault_node


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_deferred_fault(seed):
    """A 25% slowdown, half absorbed by the peers' baseline before the
    node latches, is DEFER: the swap lands at the next checkpoint
    boundary and the restart comes from the hook's pending path."""
    program, ref, rhook = drive(seed, 400, {"factor": 1.25}, onset=60,
                                ckpt_every=25, spike_p=0.0, jitter=0.005)
    acts = {a for f in program["frames"] for _, a in f["decisions"]}
    assert guard_decisions.DEFER in acts
    assert any(s % 25 == 0 for s in program["restarts"])
    assert rhook.hook.node_id != rhook.fault_node
    assert guard_decisions.compare(program, ref) == ZERO


def test_an_altered_decision_is_caught():
    program, ref, _ = drive(4, 300, {"factor": 1.5}, onset=60)
    program["restarts"] = program["restarts"][1:] + [10**6]
    assert guard_decisions.compare(program, ref)["restart_mismatch"] > 0
    program, ref, _ = drive(4, 300, {"factor": 1.5}, onset=60)
    f = next(f for f in program["frames"] if f["decisions"])
    f["decisions"] = []
    f["flagged"] = ~f["flagged"]
    out = guard_decisions.compare(program, ref)
    assert out["decision_mismatch"] == 1 and out["verdict_mismatch"] > 0
