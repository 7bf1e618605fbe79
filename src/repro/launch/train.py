"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Trains one architecture with Guard in the loop. ``repro.guard.GuardStepHook``
turns the measured step times into telemetry Frames for the peer-relative
detector and the tiered policy, and a ``TieredCheckpointManager`` keeps the
peer-replica and node-local snapshots, so an IMMEDIATE-tier restart resumes
from the fastest tier: the closed loop of Fig. 1 at single-host scale.

Sizes: by default a reduced smoke config; ``--layers N`` keeps the
published widths and cuts the depth to N layers (the missing layers would
sit on further pipeline stages); ``--full`` trains the published config.
``chip_smoke.py`` drives the same ``train`` function on one TPU chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import shutil
import sys
import tempfile
from typing import Any, Callable, Dict, Optional

import numpy as np

from repro.configs import ArchConfig, get_config, reduced
from repro.guard import GuardStepHook
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.train import (AdamWConfig, DataConfig, SyntheticLM,
                         TieredCheckpointManager, TrainConfig, Trainer)


def build_config(arch: str, *, full: bool = False,
                 layers: int = 0) -> ArchConfig:
    """The published config (``full``), its widths at ``layers`` layers,
    or the reduced smoke config."""
    cfg = get_config(arch)
    if layers:
        return dataclasses.replace(cfg, num_layers=layers)
    return cfg if full else reduced(cfg)


def train(cfg: ArchConfig, *, steps: int, seq_len: int, batch: int,
          ckpt_dir: str, ckpt_interval: int = 50, lr: float = 3e-4,
          microbatch: int = 0, seed: int = 0,
          hook: Optional[GuardStepHook] = None,
          on_metrics: Optional[Callable[[int, dict], None]] = None
          ) -> Dict[str, Any]:
    """Run one guarded training job from random weights made from
    ``seed``. Returns ``Trainer.run``'s result plus the ``trainer``, the
    ``hook`` and the checkpoint manager (``ckpt``)."""
    model = Model(cfg)
    data = SyntheticLM(DataConfig(cfg.vocab_size, seq_len, batch,
                                  seed=seed))
    hook = hook if hook is not None else GuardStepHook(seed=seed)
    ckpt = TieredCheckpointManager(ckpt_dir, node_id=hook.node_id)
    hook.bind_checkpoint(ckpt)
    trainer = Trainer(
        model, data,
        TrainConfig(steps=steps, ckpt_interval=ckpt_interval,
                    microbatch=microbatch,
                    opt=AdamWConfig(peak_lr=lr,
                                    warmup_steps=max(steps // 20, 1),
                                    total_steps=steps)),
        ckpt=ckpt, hook=hook, seed=seed)
    out = trainer.run(on_metrics=on_metrics)
    return {**out, "trainer": trainer, "hook": hook, "ckpt": ckpt}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--full", action="store_true",
                    help="use the full published config (TPU-scale)")
    ap.add_argument("--layers", type=int, default=0,
                    help="published widths cut to this many layers")
    ap.add_argument("--ckpt-dir", default=None,
                    help="keep checkpoints here and resume from them "
                         "(default: a temporary directory, removed)")
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = build_config(args.arch, full=args.full, layers=args.layers)
    print(f"[train] {cfg.name}: {cfg.num_layers}L d={cfg.d_model} "
          f"params={cfg.param_count()/1e6:.1f}M")

    def log(step, m):
        if step % 10 == 0 or step == 1:
            print(f"  step {step:5d}  loss {m['loss']:.4f}  "
                  f"gnorm {m['grad_norm']:.3f}  lr {m['lr']:.2e}")

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_ckpt_")
    try:
        out = train(cfg, steps=args.steps, seq_len=args.seq_len,
                    batch=args.batch, ckpt_dir=ckpt_dir,
                    ckpt_interval=args.ckpt_interval, lr=args.lr,
                    microbatch=args.microbatch, seed=args.seed,
                    on_metrics=log)
    finally:
        if args.ckpt_dir is None:
            shutil.rmtree(ckpt_dir, ignore_errors=True)
    losses = [h["loss"] for h in out["history"]]
    walls = [h["wall_s"] for h in out["history"]]
    print(f"[train] done: {out['final_step']} steps, "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}, "
          f"median step {np.median(walls)*1e3:.0f} ms, "
          f"guard restarts {out['hook'].restarts_requested}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
