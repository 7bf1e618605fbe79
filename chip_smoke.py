"""Proof that Guard's main path runs on one TPU chip.

Run from the checkout root:

    python chip_smoke.py                 # one chip: phases a, b, c
    python chip_smoke.py --four-chips    # four chips: the sharded paths

Phases, one process, each printing its own numbers:

  a. train   phi3-mini-3.8b at its published widths (d_model 3072, 32
             heads x 96, d_ff 8192, vocab 32064), 4 of its 32 layers (the
             other 28 would sit on further pipeline stages), batch
             2 x 2,048, random weights from --seed, through the
             launcher's ``train`` with ``repro.guard.GuardStepHook`` and a
             ``TieredCheckpointManager``. One injected stall must be
             flagged by the detector and the job must resume from the
             peer or local tier.
  b. detect  ``StragglerDetector`` with the compiled pallas scorer at
             16,384 and 131,072 nodes, fed as benchmarks/bench_scale.py
             feeds it; every verdict array must equal the numpy scorer's.
  c. probe   the sweep's compute probe (``LocalJaxSweepBackend``).

``--four-chips`` runs only what exists across chips: one FSDP train step
of the same model on a (4, 1) mesh against the one-chip step on the same
batch, and the sharded ``scorer="jax"`` detector at 131,072 nodes against
numpy. Any failure raises; there is no CPU fallback. The last line is the
JSON result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "phi3-mini-3.8b"
LAYERS = 4
BATCH, SEQ = 2, 2048
STEPS, STALL_AT = 40, 20
# four-chip comparison: the same 4,096 tokens as 4 x 1,024 so the batch
# splits one sequence per chip; the one-chip reference must still fit
MESH_BATCH, MESH_SEQ = 4, 1024
# bf16 compute, f32 accumulation: the sharded and one-chip steps differ
# only in the order of the loss mean's reduction, far inside one bf16 ulp
LOSS_RTOL = 1e-3
DETECT_NODES = (16384, 131072)
# TPU float32 division need not round as IEEE does: the slowdown
# magnitude x / median - 1 may differ from numpy's by ulps of the quotient
SLOWDOWN_ULPS = 4.0
GB = 1e9


class CacheCounter:
    """Counts JAX persistent-compilation-cache hits and misses."""

    def __init__(self):
        self.hits = self.misses = 0

    def __call__(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {what}")


def compiled_kernels(fn, *args) -> int:
    """Number of Mosaic kernels in the compiled program: a kernel that
    ran interpreted would lower to plain XLA ops instead."""
    import jax
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def phase_train(seed: int) -> None:
    import jax
    import numpy as np

    from repro.guard import GuardStepHook
    from repro.launch.train import build_config, train

    cfg = build_config(ARCH, layers=LAYERS)
    print(f"[train] {cfg.name} published widths d={cfg.d_model} "
          f"heads={cfg.num_heads}x{cfg.head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size}; {cfg.num_layers} of 32 layers (the "
          f"other 28 would sit on further pipeline stages); "
          f"{cfg.param_count() / 1e6:.1f}M params; batch {BATCH} x {SEQ}",
          flush=True)
    hook = GuardStepHook(window_steps=4, n_peers=15, seed=seed)
    hook.inject_stall(at_step=STALL_AT, factor=8.0, steps=4)
    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        out = train(cfg, steps=STEPS, seq_len=SEQ, batch=BATCH,
                    ckpt_dir=ckpt_dir, ckpt_interval=STEPS + 1, seed=seed,
                    hook=hook)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    hist = out["history"]
    losses = np.asarray([h["loss"] for h in hist])
    walls = np.asarray([h["wall_s"] for h in hist])
    med = float(np.median(walls[1:]))
    events = hook.session.events()
    flags = [e for e in events if e.kind == "straggler_flagged"]
    recoveries = [e for e in events if e.kind == "recovery"]
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", 0)
    tr = out["trainer"]
    mem = tr._step_fn.lower(tr.params, tr.opt_state,
                            tr.data.batch_at(0)).compile().memory_analysis()
    print(f"[train] first step {walls[0]:.3f} s (compile + run); "
          f"compile ~ {walls[0] - med:.3f} s", flush=True)
    print(f"[train] {len(hist)} steps run to reach step "
          f"{out['final_step']}; median step {med * 1e3:.3f} ms "
          f"(ends in block_until_ready); "
          f"{BATCH * SEQ / med:.1f} tokens/s", flush=True)
    print(f"[train] loss first {losses[0]:.6f} last {losses[-1]:.6f}",
          flush=True)
    print(f"[train] peak_bytes_in_use {peak / GB:.3f} GB of "
          f"{stats.get('bytes_limit', 0) / GB:.3f} GB; compiled step: "
          f"arguments {mem.argument_size_in_bytes / GB:.3f} GB (aliased to "
          f"outputs {mem.alias_size_in_bytes / GB:.3f} GB), temporaries "
          f"{mem.temp_size_in_bytes / GB:.3f} GB", flush=True)
    print(f"[train] guard: {len(flags)} straggler flag(s), "
          f"{hook.restarts_requested} restart(s), recovery tier(s) "
          f"{[e.ckpt_tier for e in recoveries]}, replayed "
          f"{[e.replay_steps for e in recoveries]} step(s), restore "
          f"{[round(e.restore_s, 3) for e in recoveries]} s; "
          f"{out['ckpt'].snapshots_taken} fast snapshot(s)", flush=True)
    check(out["final_step"] == STEPS, "training did not reach its last step")
    check(bool(np.all(np.isfinite(losses))), "non-finite loss")
    check(losses[-1] < losses[0], "loss did not fall")
    check(0 < peak < 16 * GB, "peak device memory not under 16 GB")
    check(bool(flags) and hook.restarts_requested >= 1,
          "the injected stall was not detected")
    check(any(e.ckpt_tier in ("peer", "local") for e in recoveries),
          "the restart did not resume from a fast tier")


def phase_detect() -> None:
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.bench_scale import detector_microbench, scorer_agreement
    from repro.kernels.fleet_score import fleet_score

    mats = jnp.ones((1, 8, DETECT_NODES[0]), jnp.float32)
    kernels = compiled_kernels(lambda m: fleet_score(
        m, [1.0] * 8, 0, z_threshold=3.0, slowdown_floor=0.025,
        mad_floor_frac=0.01), mats)
    print(f"[detect] fleet_score compiled: {kernels} tpu_custom_call(s)",
          flush=True)
    check(kernels >= 1, "fleet_score did not compile to a Mosaic kernel")
    for n in DETECT_NODES:
        agree = scorer_agreement(n)
        pl = detector_microbench(n, windows=12, scorer="pallas")
        ref = detector_microbench(n, windows=12, scorer="numpy")
        print(f"[detect] {n} nodes, {agree['windows']} windows, "
              f"{agree['flagged']} flagged: verdict arrays that differ from "
              f"numpy {agree['differ']}; slowdown within "
              f"{agree['slowdown_ulps']:.2f} ulp of the quotient; window p50 "
              f"pallas {pl['ms_per_window_p50']:.3f} ms, numpy "
              f"{ref['ms_per_window_p50']:.3f} ms (host clock)", flush=True)
        check(not agree["differ"], f"pallas verdicts differ at {n}")
        check(agree["slowdown_ulps"] <= SLOWDOWN_ULPS,
              f"pallas slowdown off by more than {SLOWDOWN_ULPS} ulp")
        check(np.isfinite(pl["ms_per_window_p50"]), "no pallas timing")


def phase_probe() -> None:
    import jax.numpy as jnp

    from repro.kernels.sweep_burn import LocalJaxSweepBackend, burn

    a = jnp.ones((512, 512), jnp.float32)
    kernels = compiled_kernels(lambda a, b: burn(a, b, iters=64), a, a)
    check(kernels >= 1, "sweep_burn did not compile to a Mosaic kernel")
    tflops = LocalJaxSweepBackend().compute_probe(0, 0, seconds=64)
    print(f"[probe] sweep_burn 512^3 f32 x 64 chained products: "
          f"{tflops:.3f} TFLOP/s ({kernels} tpu_custom_call)", flush=True)
    check(tflops > 0, "probe measured no throughput")


def phase_four_chips(seed: int) -> None:
    import jax
    import numpy as np

    from benchmarks.bench_scale import sharded_detection
    from repro.dist import api as dist
    from repro.launch.mesh import make_cpu_mesh
    from repro.launch.train import build_config
    from repro.models.model import Model
    from repro.train import DataConfig, SyntheticLM, TrainConfig, Trainer

    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, JAX "
          f"found {len(jax.devices())}")
    cfg = build_config(ARCH, layers=LAYERS)
    model = Model(cfg)
    data = SyntheticLM(DataConfig(cfg.vocab_size, MESH_SEQ, MESH_BATCH,
                                  seed=seed))

    ref = Trainer(model, data, TrainConfig(steps=1),
                  seed=seed).run()["history"][0]
    gc.collect()                    # free device 0 for the sharded run
    mesh = make_cpu_mesh()
    with mesh, dist.use_mesh(mesh) as ctx:
        want = dist.param_sharding(model.param_axes(), model.param_shapes(),
                                   ctx)
        tr = Trainer(model, data, TrainConfig(steps=1), seed=seed)
        leaves = jax.tree.leaves(tr.params)
        placed = all(p.sharding.is_equivalent_to(s, p.ndim) for p, s in
                     zip(leaves, jax.tree.leaves(want)))
        split = sum(len({s.device for s in p.addressable_shards}) == 4 and
                    p.addressable_shards[0].data.shape != p.shape
                    for p in leaves)
        dev0 = sum(s.data.nbytes for p in leaves for s in p.addressable_shards
                   if s.device == jax.devices()[0])
        got = tr.run()["history"][0]
    rel = abs(got["loss"] - ref["loss"]) / abs(ref["loss"])
    print(f"[4chip] mesh {dict(mesh.shape)}; params placed with their "
          f"shardings: {placed}; {split} of {len(leaves)} leaves split "
          f"across 4 chips; device 0 holds {dev0 / GB:.3f} GB of params",
          flush=True)
    print(f"[4chip] one train step, batch {MESH_BATCH} x {MESH_SEQ}: loss "
          f"one chip {ref['loss']:.6f}, (4, 1) mesh {got['loss']:.6f}, rel "
          f"diff {rel:.3e}; grad_norm {ref['grad_norm']:.6f} vs "
          f"{got['grad_norm']:.6f}; step {ref['wall_s']:.3f} s vs "
          f"{got['wall_s']:.3f} s (both include compile)", flush=True)
    check(placed and split > 0, "parameters not placed with their shardings")
    check(rel <= LOSS_RTOL, "sharded loss differs from the one-chip loss")
    sd = sharded_detection(n=DETECT_NODES[-1])
    print(f"[4chip] sharded jax scorer at {sd['n_nodes']} nodes on "
          f"{sd['n_devices']} devices {sd['mesh_shape']}: verdicts equal "
          f"numpy: {sd['verdict_parity']}; window p50 "
          f"{sd['ms_per_window_p50']:.3f} ms (host clock)", flush=True)
    check(sd["verdict_parity"], "sharded jax verdicts differ from numpy")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded paths")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform} ({dev.device_kind})")
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache = CacheCounter()
    jax.monitoring.register_event_listener(cache)
    print(f"[chip] {dev.device_kind} x {len(jax.devices())}, jax "
          f"{jax.__version__}, compile cache {cache_dir}", flush=True)
    phases = ([("4chip", lambda: phase_four_chips(args.seed))]
              if args.four_chips else
              [("train", lambda: phase_train(args.seed)),
               ("detect", phase_detect),
               ("probe", phase_probe)])
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        print(f"[chip] phase {name} passed in "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
    print(f"[chip] compile cache: {cache.hits} hit(s), {cache.misses} "
          f"miss(es)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
