"""Training loop with Guard hooks, sharded step construction, fault-tolerant
restart, and optional gradient accumulation.

``make_train_step`` builds the functional (params, opt, batch) -> (params,
opt, metrics) step used identically by the real trainer, the benchmarks and
the multi-pod dry-run. When a mesh context is active, in/out shardings are
derived from the parameter trees' logical axes (see repro.dist.api), so the
same code path covers single-CPU smoke tests and the 512-chip production
mesh.

Guard integration: the trainer reports its per-step wall time (each host's
time-to-barrier in a real deployment) to a ``StepHook``; when the hook
requests a restart — Guard's IMMEDIATE tier — the trainer restores the last
checkpoint, notifies the hook via ``on_restart`` (if present) so partial
telemetry windows are dropped, and continues: exactly the closed-loop
behaviour in Fig. 1. ``repro.guard.GuardStepHook`` is the production
implementation — it turns these wall times into telemetry Frames and runs
them through the real monitor → policy → manager pipeline.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Protocol

import jax
import jax.numpy as jnp

from repro import tracing
from repro.dist import api as dist
from repro.models.model import Model
from repro.train.checkpoint import CheckpointManager
from repro.train.data import SyntheticLM
from repro.train.optimizer import AdamWConfig, apply_adamw, init_opt_state


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 100
    ckpt_interval: int = 50
    log_interval: int = 10
    microbatch: int = 0          # >0: grad-accumulation chunk (batch dim)
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)


def make_train_step(model: Model, opt_cfg: AdamWConfig,
                    microbatch: int = 0) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def loss_fn(params, batch):
        loss, metrics = model.train_loss(params, batch)
        return loss, metrics

    def grads_of(params, batch):
        if not microbatch:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return grads, metrics
        # gradient accumulation over batch-dim chunks via scan
        B = batch["tokens"].shape[0]
        assert B % microbatch == 0, (B, microbatch)
        n = B // microbatch

        def split(x):
            return x.reshape((n, microbatch) + x.shape[1:]) \
                if x.ndim and x.shape[0] == B else \
                jnp.broadcast_to(x, (n,) + x.shape)

        chunks = {k: split(v) for k, v in batch.items()}

        def body(acc, chunk):
            (loss, metrics), g = jax.value_and_grad(
                loss_fn, has_aux=True)(params, chunk)
            acc = jax.tree.map(jnp.add, acc, g)
            return acc, metrics

        zero = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        acc, metrics = jax.lax.scan(body, zero, chunks)
        grads = jax.tree.map(lambda g: g / n, acc)
        metrics = jax.tree.map(lambda m: m.mean(), metrics)
        return grads, metrics

    def train_step(params, opt_state, batch):
        grads, metrics = grads_of(params, batch)
        params, opt_state, opt_metrics = apply_adamw(
            params, grads, opt_state, opt_cfg)
        metrics = {**metrics, **opt_metrics}
        return params, opt_state, metrics

    return train_step


def make_serve_step(model: Model) -> Callable:
    """(params, tokens, cache) -> (logits, cache) — the decode-shape step."""
    def serve_step(params, tokens, cache):
        return model.decode_step(params, tokens, cache)
    return serve_step


class StepHook(Protocol):
    """Guard-side per-step callback. Return True to request a restart."""

    def __call__(self, step: int, wall_s: float,
                 metrics: Dict[str, float]) -> bool: ...


class Trainer:
    def __init__(self, model: Model, data: SyntheticLM, cfg: TrainConfig,
                 ckpt: Optional[CheckpointManager] = None,
                 hook: Optional[StepHook] = None,
                 seed: int = 0):
        self.model = model
        self.data = data
        self.cfg = cfg
        self.ckpt = ckpt
        self.hook = hook
        self.seed = seed
        self.history: list = []
        self.last_recovery: Optional[Dict[str, Any]] = None

        self.axes = model.param_axes()
        p_sh = o_sh = None
        ctx = dist.current()
        if ctx is not None:
            p_sh = dist.param_sharding(self.axes, model.param_shapes(), ctx)
            o_sh = {"mu": p_sh, "nu": p_sh, "count": ctx.sharding((), ())}
        # initialize where the state lives: under a mesh every leaf is
        # born with its own sharding instead of whole on device 0
        self.params = jax.jit(lambda key: model.init_params(key)[0],
                              out_shardings=p_sh)(jax.random.key(seed))
        self.opt_state = jax.jit(init_opt_state,
                                 out_shardings=o_sh)(self.params)
        self._step_fn = self._build_step(p_sh, o_sh)

    def _build_step(self, p_sh, o_sh):
        """Params and optimizer state are donated with or without a mesh:
        the step's outputs reuse their buffers, so the state is held once
        on the device, not twice. Snapshots copy to the host first
        (``TieredCheckpointManager.save_fast``), and a restore hands in
        host arrays, so nothing reads a donated buffer."""
        step = make_train_step(self.model, self.cfg.opt, self.cfg.microbatch)
        if p_sh is None:
            return jax.jit(step, donate_argnums=(0, 1))
        b_sh = None  # batch sharding constrained inside the model
        return jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                       out_shardings=(p_sh, o_sh, None),
                       donate_argnums=(0, 1))

    # ------------------------------------------------------------- loop

    def restore(self) -> int:
        """Restore from the fastest checkpoint tier available. With a
        ``TieredCheckpointManager`` this is the hot-spare path: the peer
        replica or local shard serves before durable storage; the tier
        used is recorded in ``self.last_recovery``."""
        if self.ckpt is None:
            return 0
        restore_any = getattr(self.ckpt, "restore_any", None)
        if restore_any is not None:
            out = restore_any(self.params, self.opt_state)
            if out is None:
                return 0
            self.params, self.opt_state, step, tier = out
            self.last_recovery = {"step": step, "ckpt_tier": tier.value,
                                  "hot_spare": tier.value == "peer"}
            return step
        out = self.ckpt.restore(self.params, self.opt_state)
        if out is None:
            return 0
        self.params, self.opt_state, step = out
        self.last_recovery = {"step": step, "ckpt_tier": "cold",
                              "hot_spare": False}
        return step

    def run(self, on_metrics: Optional[Callable[[int, dict], None]] = None
            ) -> Dict[str, Any]:
        step = self.restore()
        while step < self.cfg.steps:
            with tracing.span("train.batch"):
                batch = self.data.batch_at(step)
            t0 = time.perf_counter()
            # on a replayed step the dispatch only enqueues the restored
            # host state's copy to the device; the wait holds it
            with tracing.span("train.dispatch"):
                self.params, self.opt_state, metrics = self._step_fn(
                    self.params, self.opt_state, batch)
            with tracing.span("train.wait"):
                jax.block_until_ready(metrics["loss"])
            wall = time.perf_counter() - t0
            step += 1
            with tracing.span("train.metrics"):
                m = {k: float(v) for k, v in metrics.items()}
                self.history.append({"step": step, "wall_s": wall, **m})
            if on_metrics:
                on_metrics(step, m)

            boundary = bool(self.ckpt) and \
                step % self.cfg.ckpt_interval == 0
            if self.ckpt:
                with tracing.span("train.snapshot"):
                    # fast-tier snapshots (tiered manager only): peer
                    # replica + local shard on the MTTF-tuned cadence
                    on_step = getattr(self.ckpt, "on_step", None)
                    if on_step:
                        on_step(step, self.params, self.opt_state)
                    if boundary:
                        self.ckpt.save(step, self.params, self.opt_state)

            if boundary:
                # checkpoint boundary: Guard lands deferred mitigations
                # here (the hook may request a restart on the next step)
                on_ckpt = getattr(self.hook, "on_checkpoint", None)
                if on_ckpt:
                    with tracing.span("train.hook"):
                        on_ckpt(step)

            restart = False
            if self.hook:
                with tracing.span("train.hook"):
                    restart = self.hook(step, wall, m)
            if restart:
                # Guard requested an immediate restart: rewind to the last
                # checkpoint (replacement happens at the cluster layer)
                fail_step = step
                t_restore = time.perf_counter()
                with tracing.span("train.restore"):
                    step = self.restore()
                restore_wall = time.perf_counter() - t_restore
                on_restart = getattr(self.hook, "on_restart", None)
                if on_restart:
                    on_restart(step)
                on_recovery = getattr(self.hook, "on_recovery", None)
                if on_recovery:
                    info = dict(self.last_recovery or
                                {"ckpt_tier": "cold", "hot_spare": False})
                    info["restore_s"] = restore_wall
                    info["replay_steps"] = max(fail_step - step, 0)
                    on_recovery(step, info)
        if self.ckpt:
            self.ckpt.wait()
        return {"final_step": step, "history": self.history}
