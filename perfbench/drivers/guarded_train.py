"""Driver of the guarded-training cells: one job through the program's
normal path (``Trainer`` + ``GuardStepHook`` + ``TieredCheckpointManager``,
wired as ``repro.launch.train.train`` wires them), at the configuration's
published widths, from weights and batches that the benchmark makes from
the seed.

Set-up builds the trainer once and drives it from the seed through its
first ``setup_steps`` steps, through the window's own call and feed; the
same trainer then fills the measured window. Set-up includes the step's
compilation, the fast snapshot the checkpoint manager takes at step 1
(the peer replica in host memory and the node-local shard, 7.8 GB
written to a directory under ``TMPDIR`` that the run deletes, flushed
to the disk before the window opens), and ``warmup_windows`` + 3
windows scored by Guard.

After the window: the Guard decisions are replayed by
``references/guard_decisions.py`` from the step times this run recorded,
and the first three steps by ``references/dense_lm.py`` in float32.
"""
from __future__ import annotations

import dataclasses
import os
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from perfbench import flops, traffic
from perfbench.references import dense_lm, guard_decisions
from perfbench.trace import TracedWindow

HOST_SPANS = ("guard_hook", "data.batch_at", "ckpt.on_step",
              "trainer.restore", "fault.sleep")


class _WindowClosed(Exception):
    """Raised from the metrics callback to end the run at the window's
    close; the step that closed it is complete, its hook call is not
    made."""


class RecordingHook:
    """Wraps the program's step hook: records every step time it is told
    and every checkpoint boundary and restart, times the hook's host
    work, and plays the mix's fault.

    A fault slows the node it was injected on by ``factor`` from step
    ``onset``: the step takes ``factor`` x its time on the host clock
    (the difference is slept after the step) and the hook is told that
    time. The fault belongs to the node id and ends when Guard swaps the
    node out, on either restart path.
    """

    def __init__(self, hook, spans, fault: Optional[dict] = None,
                 onset: int = 0, sleep: bool = True):
        self.hook = hook
        self.spans = spans
        self.fault_node = hook.node_id if fault else None
        self.factor = fault["factor"] if fault else 1.0
        self.onset = onset
        self.sleep = sleep
        self.log: List[tuple] = []
        self.restart_steps: List[int] = []
        self.restart_t: List[float] = []

    def faulted(self, step: int) -> bool:
        return self.fault_node is not None and step >= self.onset and \
            self.hook.node_id == self.fault_node

    def __call__(self, step: int, wall_s: float, metrics) -> bool:
        told = wall_s
        if self.faulted(step):
            told = wall_s * self.factor
            if self.sleep:
                with self.spans.span("fault.sleep"):
                    time.sleep(told - wall_s)
        self.log.append(("call", step, told))
        with self.spans.span("guard_hook"):
            out = self.hook(step, told, metrics)
        if out:
            self.restart_steps.append(step)
            self.restart_t.append(time.perf_counter())
        return out

    def on_checkpoint(self, step: int) -> None:
        self.log.append(("ckpt", step))
        self.hook.on_checkpoint(step)

    def __getattr__(self, name):
        return getattr(self.hook, name)


def record_decisions(hook) -> List[dict]:
    """Record what the program's detector and policy produced for every
    frame the hook fed them (instance-level wrappers, no program edit)."""
    frames: List[dict] = []
    det = hook.session.monitor.detector
    pol = hook.session.monitor.policy
    update, decide = det.update, pol.decide

    def rec_update(frame):
        fa = update(frame)
        st = np.asarray(frame.metrics["step_time"], np.float64)
        frames.append({"step": int(frame.step), "own": float(st[0]),
                       "peers": st[1:].copy(),
                       "ids": np.asarray(frame.node_ids).copy(),
                       "flagged": fa.flagged.copy(),
                       "step_deviant": fa.step_deviant.copy(),
                       "stalled": fa.stalled.copy(),
                       "slowdown": fa.slowdown.copy(), "decisions": []})
        return fa

    def rec_decide(fa):
        out = decide(fa)
        ids = frames[-1]["ids"]
        frames[-1]["decisions"] = [
            (int(np.flatnonzero(ids == d.node_id)[0]), d.action.value)
            for d in out]
        return out

    det.update, pol.decide = rec_update, rec_decide
    return frames


def check_guard_settings(hook, guard: dict) -> None:
    """The program runs Guard as the configuration states it."""
    det = dataclasses.asdict(hook.session.monitor.detector.cfg)
    pol = dataclasses.asdict(hook.session.monitor.policy.cfg)
    want_det = guard["detector"]
    if {k: det[k] for k in want_det} != want_det or \
            {k: pol[k] for k in guard["policy"]} != guard["policy"] or \
            hook.window_steps != guard["window_steps"] or \
            hook.warmup_windows != guard["warmup_windows"] or \
            len(hook.peer_ids) != guard["n_peers"] or \
            hook.session.manager.pending_patience_s != \
            guard["pending_patience_s"]:
        raise RuntimeError(f"Guard runs otherwise than the configuration "
                           f"states: detector {det}, policy {pol}")


def build_hook(cfg: dict, seed: int):
    from repro.core.detector import DetectorConfig
    from repro.guard import GuardStepHook
    g = cfg["guard"]
    hook = GuardStepHook(window_steps=g["window_steps"],
                         n_peers=g["n_peers"], n_spares=g["n_spares"],
                         peer_jitter=g["peer_jitter"],
                         warmup_windows=g["warmup_windows"],
                         seed=seed % 2**32,
                         detector_cfg=DetectorConfig(**g["detector"]))
    check_guard_settings(hook, g)
    return hook


class SeededBatches:
    """The job's input pipeline: ``traffic.token_batch`` from the seed."""

    def __init__(self, cfg: dict, seed: int, spans):
        self.cfg, self.seed, self.spans = cfg, seed, spans

    def batch_at(self, step: int, shard: int = 0, num_shards: int = 1):
        with self.spans.span("data.batch_at"):
            # Trainer asks for the batch of the step it is about to take
            return traffic.token_batch(self.seed, step + 1,
                                       self.cfg["batch"],
                                       self.cfg["seq_len"],
                                       self.cfg["vocab_size"])


def _program_arch(cfg: dict):
    """The program's architecture config holding the configuration's
    sizes (its registered config, as the configuration file states it)."""
    from repro.configs import get_config
    arch = dataclasses.replace(
        get_config(cfg["program_arch"]),
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"])
    if arch.act != "swiglu" or arch.window or arch.qk_norm or \
            arch.qkv_bias or arch.moe is not None:
        raise RuntimeError(f"{arch.name} is not the plain dense decoder "
                           f"that references/dense_lm.py describes")
    return arch


def _leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
              keep: Optional[set] = None) -> float:
    """Worst leaf's |prog norm - ref norm| over the larger of the ref
    leaf's norm and the median ref leaf norm."""
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in names]))
    return max(abs(prog[n] - ref[n]) / max(ref[n], med) for n in names)


def compare_training(prog: dict, ref: dict, b1: float) -> Dict[str, float]:
    """Numbers compared with the float32 reference's first three steps.
    Leaves whose reference gradient is nought to rounding (under a
    thousandth of the median leaf's) are left out of the change."""
    losses = prog["losses"]
    loss_rel = max(abs(a - b) / abs(b) for a, b in zip(losses, ref["losses"]))
    grad = {n: v / (1 - b1) for n, v in prog["mu1"].items()}
    med = float(np.median(list(ref["grad"].values())))
    moving = {n for n, v in ref["grad"].items() if v >= 1e-3 * med}
    return {"loss_rel": loss_rel,
            "grad_leaf_gap": _leaf_gap(grad, ref["grad"]),
            "change_leaf_gap": _leaf_gap(prog["change"], ref["change"],
                                         moving)}


def build_trainer(cfg: dict, seed: int, spans, steps: int, hook=None,
                  ckpt=None):
    """The program's ``Trainer`` for the configuration, holding the
    benchmark's weights (made from the seed) and batches."""
    import jax

    from repro.models.model import Model
    from repro.train import AdamWConfig, TrainConfig, Trainer
    opt = cfg["optimizer"]
    trainer = Trainer(
        Model(_program_arch(cfg)), SeededBatches(cfg, seed, spans),
        TrainConfig(steps=steps, ckpt_interval=1 << 62,
                    opt=AdamWConfig(**{k: opt[k] for k in (
                        "peak_lr", "min_lr_frac", "warmup_steps",
                        "total_steps", "b1", "b2", "eps", "weight_decay",
                        "grad_clip")})),
        ckpt=ckpt, hook=hook, seed=seed % 2**31)
    shapes = jax.tree.map(lambda a: tuple(a.shape), trainer.params)
    if shapes != jax.tree.map(tuple, dense_lm.param_shapes(cfg),
                              is_leaf=lambda x: isinstance(x, tuple)):
        raise RuntimeError("program parameter tree differs from the "
                           "configuration's")
    for leaf in jax.tree.leaves(trainer.params):
        leaf.delete()
    trainer.params = dense_lm.init_params(cfg, seed)
    return trainer


class FirstSteps:
    """Metrics callback that keeps what the reference compares: the
    losses of steps 1-3, per-leaf norms of Adam's first moment after
    step 1 (the clipped first gradient times 1 - b1), and per-leaf norms
    of the parameters' change after step 3, while step 4 has not yet
    overwritten them."""

    def __init__(self, trainer, cfg: dict, seed: int):
        self.trainer, self.cfg, self.seed = trainer, cfg, seed
        self.calls = 0
        self.numbers = {"losses": [], "mu1": None, "change": None}

    def __call__(self, step: int, m: dict) -> None:
        self.calls += 1
        if self.calls <= 3:
            self.numbers["losses"].append(m["loss"])
        if self.calls == 1:
            self.numbers["mu1"] = dense_lm.leaf_norms(
                self.trainer.opt_state["mu"])
        if self.calls == 3:
            self.numbers["change"] = dense_lm.change_norms(
                self.trainer.params, self.cfg, self.seed)


def run(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
        trace: bool, t0: float, spans) -> dict:
    import jax

    from repro.train.checkpoint import TieredCheckpointManager
    opt = cfg["optimizer"]
    s0 = cfg["setup_steps"]
    tokens_per_step = cfg["batch"] * cfg["seq_len"]
    fault = mix.get("fault")
    hook = build_hook(cfg, seed)
    frames = record_decisions(hook)
    rhook = RecordingHook(hook, spans, fault,
                          onset=s0 + fault["onset_step"] if fault else 0)
    # the node-local shard of the fast snapshot (7.8 GB) goes under
    # TMPDIR, deleted as soon as the job stops, whatever ended it
    with tempfile.TemporaryDirectory(prefix="perfbench_ckpt_",
                                     ignore_cleanup_errors=True) as ckpt_dir:
        ckpt = TieredCheckpointManager(ckpt_dir, node_id=hook.node_id)
        hook.bind_checkpoint(ckpt)
        trainer = build_trainer(cfg, seed, spans, 1 << 62, rhook, ckpt)

        on_step, restore = ckpt.on_step, trainer.restore

        def timed_on_step(*a, **k):
            with spans.span("ckpt.on_step"):
                return on_step(*a, **k)

        def timed_restore():
            with spans.span("trainer.restore"):
                return restore()

        ckpt.on_step, trainer.restore = timed_on_step, timed_restore

        first = FirstSteps(trainer, cfg, seed)
        done_t: List[tuple] = []          # (step, host time of completion)
        losses_at: Dict[int, List[float]] = {}
        st = {"w0": None}
        traced = TracedWindow(trace, spans)

        def on_metrics(step: int, m: dict) -> None:
            now = time.perf_counter()
            done_t.append((step, now))
            losses_at.setdefault(step, []).append(m["loss"])
            first(step, m)
            if step == s0 and st["w0"] is None:
                st["setup_snapshot_s"] = spans.total("ckpt.on_step")
                # the set-up snapshot's node-local shard reaches the disk
                # before the window opens: its writeback is set-up's
                t = time.perf_counter()
                os.sync()
                st["setup_flush_s"] = time.perf_counter() - t
                spans.durations.clear()
                traced.start()
                st["w0"] = time.perf_counter()
            elif st["w0"] is not None and now - st["w0"] >= seconds:
                raise _WindowClosed

        try:
            trainer.run(on_metrics=on_metrics)
        except _WindowClosed:
            pass
        traced.stop()
    w1 = done_t[-1][1]
    w0 = st["w0"]
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    summary = traced.summary(HOST_SPANS)

    # ---- what the window measured
    win = [(s, t) for s, t in done_t if t > w0]
    final_step = win[-1][0]
    goodput = (final_step - s0) * tokens_per_step / (w1 - w0)
    times = [w0] + [t for _, t in win]
    intervals = np.diff(times)
    e2e = {"goodput_tokens_per_s": goodput,
           "step_ms_p90": float(np.percentile(intervals, 90)) * 1e3,
           "setup_s": w0 - t0}
    layer = {"hook_ms_per_step":
             1e3 * spans.total("guard_hook") / max(len(win), 1),
             "train_flops_per_token": flops.dense_lm_train_flops_per_token(
                 cfg, cfg["seq_len"]),
             "goodput_tokens_per_s": goodput,
             "setup_snapshot_s": st["setup_snapshot_s"],
             "setup_flush_s": st["setup_flush_s"],
             # the window's three longest step intervals: (step, ms)
             "longest_intervals": sorted(
                 ((win[i][0], 1e3 * float(intervals[i]))
                  for i in np.argsort(intervals)[-3:]),
                 key=lambda x: -x[1]),
             "span_max_ms": {k: 1e3 * max(v)
                             for k, v in spans.durations.items()}}
    swaps = [e for e in hook.session.events() if e.kind == "swap"]
    accuracy = {"false_evictions": sum(e.old != rhook.fault_node
                                       for e in swaps),
                "misses": int(fault is not None and not any(
                    e.old == rhook.fault_node for e in swaps))}
    if fault:
        onset = rhook.onset
        before = [t for s, t in done_t if s == onset - 1]
        t_fault = before[0] if before else w1
        r_ix = [i for i, s in enumerate(rhook.restart_steps) if s >= onset]
        if r_ix:
            t_req = rhook.restart_t[r_ix[0]]
            after = [(s, t) for s, t in done_t if t > t_req]
            passed = [t for s, t in after if s >= onset]
            e2e["detect_s"] = t_req - t_fault
            layer["recover_s"] = (passed[0] if passed else w1) - t_fault
            healthy = [b - a for (_, a), (_, b) in zip(done_t, done_t[1:])
                       if w0 < a and b < t_fault]
            layer["restore_s"] = after[0][1] - t_req - float(
                np.median(healthy))
            layer["restore_host_s"] = spans.total("trainer.restore")
            layer["detect_steps"] = rhook.restart_steps[r_ix[0]] - onset + 1
        else:
            e2e["detect_s"] = w1 - t_fault     # censored: a miss
    counters = {"window_steps_completed": len(win),
                "restarts": len(rhook.restart_steps),
                "final_step": final_step}

    # ---- correctness: free the program's state, then the references
    trainer.params = trainer.opt_state = None
    ckpt.drop_peer()
    program = {"frames": frames, "restarts": rhook.restart_steps}
    guard_ref = guard_decisions.replay(rhook.log, [f["peers"] for f in frames],
                                       cfg["guard"])
    checks = guard_decisions.compare(program, guard_ref)
    ref = dense_lm.Reference(cfg).run(
        seed, [traffic.token_batch(seed, i, cfg["batch"], cfg["seq_len"],
                                   cfg["vocab_size"]) for i in (1, 2, 3)])
    checks.update(compare_training(first.numbers, ref, opt["b1"]))
    all_losses = [x for v in losses_at.values() for x in v]
    checks["nonfinite_losses"] = int(np.sum(~np.isfinite(all_losses)))
    checks["replay_loss_gap"] = max(
        [abs(x - v[0]) for v in losses_at.values() for x in v[1:]] or [0.0])
    return {"e2e": e2e, "layer": layer, "checks": checks,
            "accuracy": accuracy, "counters": counters, "trace": summary,
            "attempted": len(win), "failed": checks["nonfinite_losses"],
            "memory_peak_bytes": peak,
            "window": (w0, w1)}
