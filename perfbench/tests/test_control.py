"""The lower-precision controls fail the comparison that decides
``correct``: the float32 reference computed in the precision below the
one the configuration states, put in the program's place."""
import numpy as np

from perfbench import traffic
from perfbench.drivers.guarded_train import compare_training
from perfbench.references import dense_lm, fleet_verdicts
from perfbench.run import judge
from perfbench.tests import tiny


def as_program(ref_out: dict, b1: float) -> dict:
    """A reference's numbers in the shape of the program's record."""
    return {"losses": ref_out["losses"], "change": ref_out["change"],
            "mu1": {n: v * (1 - b1) for n, v in ref_out["grad"].items()}}


def test_fp8_control_fails_the_training_comparison():
    cfg = tiny.train_config()
    seed = 2**31 + 11
    batches = [traffic.token_batch(seed, i, cfg["batch"], cfg["seq_len"],
                                   cfg["vocab_size"]) for i in (1, 2, 3)]
    ref = dense_lm.Reference(cfg).run(seed, batches)
    ctl = dense_lm.Reference(cfg, precision="fp8").run(seed, batches)
    b1 = cfg["optimizer"]["b1"]
    sound = compare_training(as_program(ref, b1), ref, b1)
    assert max(sound.values()) < 1e-6
    numbers = compare_training(as_program(ctl, b1), ref, b1)
    ok, checks = judge(cfg, {"checks": numbers})
    assert not ok, checks


def test_bf16_control_fails_the_fleet_comparison():
    cfg = tiny.fleet_config(hosts=512)
    seed = 2**31 + 12
    rows = cfg["hosts"] * cfg["accelerators_per_host"]
    mix = tiny.mix("sparse")
    frames = traffic.fleet_frames(seed, rows, mix, 12)
    args = (lambda g: frames[g], cfg["metrics"], cfg["detector"],
            cfg["policy"])
    ref = fleet_verdicts.FleetReference(*args)
    ctl = fleet_verdicts.FleetReference(*args, precision="bfloat16")
    total = {"verdict_mismatch": 0, "decision_mismatch": 0,
             "slowdown_ulps": 0.0}
    for g in range(8, 12):
        c = fleet_verdicts.compare(ctl.at(g), ref.at(g))
        for k in total:
            total[k] = max(total[k], c[k]) if k == "slowdown_ulps" \
                else total[k] + c[k]
        same = fleet_verdicts.compare(ref.at(g), ref.at(g))
        assert same["verdict_mismatch"] == 0 and same["slowdown_ulps"] == 0
    ok, checks = judge(cfg, {"checks": total})
    assert not ok, checks
    assert np.isfinite(total["slowdown_ulps"])
