"""Readings that the limits of ``correct`` are set from, on the chip, at
each cell's own size: the program's numbers on many seeds (the lower
reading), the lower-precision control's and the planted faults' (the
upper reading). The benchmark's own runs do not run this.

    python3 perfbench/tools/readings.py train <seeds> <out.jsonl>
    python3 perfbench/tools/readings.py fleet <cell> <seeds> <out.jsonl>

``train``: the program's first three steps through the cell's trainer,
against the float32 reference; the same reference computed with
float8 matmuls as float8 training runs them (the control of a
configuration that states bfloat16 compute) and from half of each batch
(a planted fault), each put in the program's place. ``fleet``: a short
window of the cell's driver per seed, and on its frames the reference
computed in bfloat16 (the control of float32 scoring) against float32.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def seeds(n: int):
    return [3_000_000_019 + 104_729 * k for k in range(n)]


def as_program(ref_out: dict, b1: float) -> dict:
    return {"losses": ref_out["losses"], "change": ref_out["change"],
            "mu1": {k: v * (1 - b1) for k, v in ref_out["grad"].items()}}


def train(n: int, out) -> None:
    import jax

    from perfbench import run, traffic
    from perfbench.drivers import guarded_train as gt
    from perfbench.references import dense_lm
    from perfbench.spans import Spans
    from repro.train.optimizer import init_opt_state

    run.enable_cache()
    cfg = run.load_json("perfbench", "configs", "phi3-mini-3.8b-4L.json")
    b1 = cfg["optimizer"]["b1"]
    refs = {"ref": dense_lm.Reference(cfg),
            "control_fp8": dense_lm.Reference(cfg, precision="fp8"),
            "fault_half_batch": dense_lm.Reference(cfg, half_batch=True)}
    trainer = None
    init_opt = jax.jit(init_opt_state)
    for seed in seeds(n):
        t0 = time.perf_counter()
        spans = Spans()
        if trainer is None:
            trainer = gt.build_trainer(cfg, seed, spans, 3)
        else:
            trainer.params = dense_lm.init_params(cfg, seed)
            trainer.opt_state = init_opt(trainer.params)
            trainer.data = gt.SeededBatches(cfg, seed, spans)
        first = gt.FirstSteps(trainer, cfg, seed)
        trainer.run(on_metrics=first)
        trainer.params = trainer.opt_state = None
        batches = [traffic.token_batch(seed, i, cfg["batch"],
                                       cfg["seq_len"], cfg["vocab_size"])
                   for i in (1, 2, 3)]
        outs = {k: r.run(seed, batches) for k, r in refs.items()}
        row = {"seed": seed,
               "program": gt.compare_training(first.numbers, outs["ref"],
                                              b1)}
        for k in ("control_fp8", "fault_half_batch"):
            row[k] = gt.compare_training(as_program(outs[k], b1),
                                         outs["ref"], b1)
        row["losses"] = {"program": first.numbers["losses"],
                         "ref": outs["ref"]["losses"]}
        # per-leaf norms, to see which leaf sets each worst-leaf gap
        row["leaves"] = {"program_mu1": first.numbers["mu1"],
                         "program_change": first.numbers["change"],
                         **{f"{k}_{q}": outs[k][q] for k in outs
                            for q in ("grad", "change")}}
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), file=out, flush=True)
        print(json.dumps(row), flush=True)


def fleet(cell_name: str, n: int, out) -> None:
    from perfbench import run, traffic
    from perfbench.drivers import fleet_detect
    from perfbench.references import fleet_verdicts
    from perfbench.spans import Spans

    run.enable_cache()
    bench = run.load_json("BENCHMARK.json")
    cell, cfg, mix = run.cell_spec(bench, cell_name)
    rows = cfg["hosts"] * cfg["accelerators_per_host"]
    for seed in seeds(n):
        res = fleet_detect.run(cell, cfg, mix, seed, 3.0, False,
                               time.perf_counter(), Spans())
        faults = traffic.fleet_faults(seed, rows, mix)
        frames = {}

        def frame(g):
            k = g % cfg["frame_pool"]
            if k not in frames:
                frames[k] = traffic.fleet_frame(seed, rows, mix, k, faults)
            return frames[k]

        args = (frame, cfg["metrics"], cfg["detector"], cfg["policy"])
        ref = fleet_verdicts.FleetReference(*args)
        ctl = fleet_verdicts.FleetReference(*args, precision="bfloat16")
        control = {"verdict_mismatch": 0, "decision_mismatch": 0,
                   "slowdown_ulps": 0.0}
        for g in range(cfg["setup_frames"], cfg["setup_frames"] + 4):
            c = fleet_verdicts.compare(ctl.at(g), ref.at(g))
            control["verdict_mismatch"] += c["verdict_mismatch"]
            control["decision_mismatch"] += c["decision_mismatch"]
            control["slowdown_ulps"] = max(control["slowdown_ulps"],
                                           c["slowdown_ulps"])
        row = {"seed": seed, "program": res["checks"],
               "control_bf16": control, "frames": res["attempted"]}
        print(json.dumps(row), file=out, flush=True)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    kind = sys.argv[1]
    with open(sys.argv[-1], "w") as fh:
        if kind == "train":
            train(int(sys.argv[2]), fh)
        else:
            fleet(sys.argv[2], int(sys.argv[3]), fh)
