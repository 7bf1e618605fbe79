import os

_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count="
os.environ["XLA_FLAGS"] = " ".join(
    [f for f in os.environ.get("XLA_FLAGS", "").split()
     if not f.startswith(_DEVICE_COUNT_FLAG)] + [_DEVICE_COUNT_FLAG + "512"])
# ^ MUST run before jax initializes a backend: the device count is fixed
#   at first init. Only the dry-run sees 512 placeholder devices, and it
#   keeps every other XLA flag the caller set.

"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For every (architecture x input shape) cell, on the single-pod 16x16 mesh
and the 2-pod 2x16x16 mesh:

  - build the full-size model functionally (ShapeDtypeStructs only — no
    allocation),
  - jit the train/prefill/serve step with explicit in/out shardings derived
    from the parameter trees' logical axes,
  - ``.lower().compile()`` — sharding mismatches, OOM-at-compile or
    unsupported collectives fail HERE,
  - print ``compiled.memory_analysis()`` (fits) and ``cost_analysis()``
    (FLOPs/bytes) and extract the §Roofline terms.

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all          # every cell, subprocess each
"""
import argparse
import json
import subprocess
import sys
import time
from typing import Optional

import jax

# the dry-run compiles for CPU placeholder devices and never takes a chip:
# pinned before any backend starts, in this process and (through this same
# module) in every --all child
jax.config.update("jax_platforms", "cpu")

from repro.configs import applicable_shapes, get_config, get_shape, \
    list_configs
from repro.dist import api as dist
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import Roofline, model_flops_for
from repro.models.model import Model, input_specs
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


def _sds(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _batch_dims(cfg, batch_struct):
    dims = {}
    for k, v in batch_struct.items():
        if k in ("tokens", "labels"):
            dims[k] = ("act_batch", None)
        elif k == "positions":
            dims[k] = (None, "act_batch", None)
        elif k in ("patch_embeds", "enc_frames"):
            dims[k] = ("act_batch", None, None)
        else:
            dims[k] = (None,) * v.ndim
    return dims


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               remat: str = "full", microbatch: int = 0,
               rules_override: Optional[dict] = None,
               fsdp_gather: bool = True):
    """Returns (lowered, meta) for one (arch x shape x mesh) cell."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = Model(cfg, remat=remat, fsdp_gather=fsdp_gather)
    rules = dict(dist.DEFAULT_RULES)
    rules.update(rules_override or {})

    with mesh, dist.use_mesh(mesh, rules) as ctx:
        param_shapes = model.param_shapes()
        axes = model.param_axes()
        p_sh = dist.param_sharding(axes, param_shapes, ctx)
        p_sds = _sds(param_shapes)
        specs = input_specs(cfg, shape)

        if shape.kind == "train":
            batch = specs["batch"]
            b_sh = dist.param_sharding(_batch_dims(cfg, batch), batch, ctx)
            o_sds = {"mu": p_sds, "nu": p_sds,
                     "count": jax.ShapeDtypeStruct((), jax.numpy.int32)}
            o_sh = {"mu": p_sh, "nu": p_sh, "count": ctx.sharding((), ())}
            step = make_train_step(model, AdamWConfig(),
                                   microbatch=microbatch)
            jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                             out_shardings=(p_sh, o_sh, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(p_sds, o_sds, batch)
        elif shape.kind == "prefill":
            batch = specs["batch"]
            b_sh = dist.param_sharding(_batch_dims(cfg, batch), batch, ctx)
            cache_struct = jax.eval_shape(
                lambda p, b: model.prefill(p, b)[1], p_sds, batch)
            cache_dims = dict(model.cache_dims())
            c_sh = dist.param_sharding(cache_dims, cache_struct, ctx)
            l_sh = ctx.sharding(("act_batch", "act_vocab"),
                                (shape.global_batch, model.vocab_padded))
            jitted = jax.jit(model.prefill, in_shardings=(p_sh, b_sh),
                             out_shardings=(l_sh, c_sh))
            lowered = jitted.lower(p_sds, batch)
        else:  # decode
            tokens = specs["tokens"]
            cache = specs["cache"]
            t_sh = ctx.sharding(("act_batch",), tokens.shape)
            c_sh = dist.param_sharding(model.cache_dims(), cache, ctx)
            l_sh = ctx.sharding(("act_batch", "act_vocab"),
                                (shape.global_batch, model.vocab_padded))
            jitted = jax.jit(model.decode_step,
                             in_shardings=(p_sh, t_sh, c_sh),
                             out_shardings=(l_sh, c_sh),
                             donate_argnums=(2,))
            lowered = jitted.lower(p_sds, tokens, cache)

    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "chips": 512 if multi_pod else 256,
            "kind": shape.kind}
    return lowered, meta, cfg, shape


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             remat: str = "full", microbatch: int = 0,
             verbose: bool = True) -> dict:
    t0 = time.time()
    lowered, meta, cfg, shape = build_cell(arch, shape_name, multi_pod,
                                           remat, microbatch)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    cost = cost if isinstance(cost, dict) else cost[0]
    chips = meta["chips"]
    # XLA's cost_analysis counts while bodies once; the static analyzer
    # multiplies through loop trip counts (see launch/hlo_analysis.py)
    mc = hlo_analysis.analyze(compiled.as_text())

    rl = Roofline(
        arch=arch, shape=shape_name, mesh=meta["mesh"], chips=chips,
        hlo_flops=mc.flops * chips, hlo_bytes=mc.traffic_bytes * chips,
        hlo_bytes_lower=mc.dot_traffic_bytes * chips,
        coll_bytes_per_chip=mc.collective_bytes,
        coll_breakdown={k: v for k, v in mc.collective_breakdown.items()},
        model_flops=model_flops_for(cfg, shape),
        peak_mem_bytes=(mem.argument_size_in_bytes +
                        mem.temp_size_in_bytes) if mem else None,
    )
    out = {**meta, "lower_s": t_lower, "compile_s": t_compile,
           "memory_analysis": {
               "argument_bytes": mem.argument_size_in_bytes,
               "output_bytes": mem.output_size_in_bytes,
               "temp_bytes": mem.temp_size_in_bytes,
               "alias_bytes": mem.alias_size_in_bytes,
           } if mem else None,
           "cost_analysis": {
               "xla_flops_per_chip": float(cost.get("flops", 0.0)),
               "xla_bytes_per_chip": float(cost.get("bytes accessed", 0.0))},
           "hlo_static": {
               "flops_per_chip": mc.flops,
               "traffic_per_chip": mc.traffic_bytes,
               "traffic_upper_per_chip": mc.traffic_bytes_upper,
               "dot_traffic_per_chip": mc.dot_traffic_bytes,
               "flops_by_comp": mc.flops_by_comp,
               "coll_by_comp": mc.coll_by_comp},
           "roofline": rl.to_dict()}
    if verbose:
        ma = out["memory_analysis"]
        print(f"[dryrun] {arch} x {shape_name} on {meta['mesh']}: "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s")
        if ma:
            print(f"  memory/chip: args {ma['argument_bytes']/2**30:.2f} GiB"
                  f" (aliased {ma['alias_bytes']/2**30:.2f}) "
                  f"temp {ma['temp_bytes']/2**30:.2f} GiB")
        print(f"  FLOPs/chip {mc.flops:.3e}  traffic/chip "
              f"{mc.traffic_bytes:.3e} (dot-only {mc.dot_traffic_bytes:.3e})"
              f"  coll bytes/chip {mc.collective_bytes:.3e}")
        print(f"  roofline: compute {rl.compute_s*1e3:.1f} ms | memory "
              f"{rl.memory_s*1e3:.1f} ms (lower "
              f"{rl.hlo_bytes_lower/(rl.chips*1e3)/819e6:.1f} ms) | "
              f"collective {rl.collective_s*1e3:.1f} ms -> "
              f"{rl.dominant}-bound, useful {rl.useful_ratio:.2f}, "
              f"roofline-fraction {rl.roofline_fraction:.2f}")
    return out


def _run_all(args) -> int:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    failures = []
    cells = []
    for arch in list_configs():
        cfg = get_config(arch)
        for shp in applicable_shapes(cfg):
            for mp in (False, True):
                cells.append((arch.replace("_", "-"), shp, mp))
    print(f"[dryrun] {len(cells)} cells")
    for arch, shp, mp in cells:
        tag = f"{arch}__{shp}__{'multi' if mp else 'single'}"
        path = os.path.join(RESULTS_DIR, tag + ".json")
        if args.resume and os.path.exists(path):
            print(f"[dryrun] skip {tag} (cached)")
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shp, "--json", path]
        if mp:
            cmd.append("--multi-pod")
        t0 = time.time()
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=args.timeout)
        ok = r.returncode == 0
        print(f"[dryrun] {tag}: {'OK' if ok else 'FAIL'} "
              f"({time.time()-t0:.0f}s)")
        if not ok:
            failures.append(tag)
            sys.stdout.write(r.stdout[-2000:] + "\n" + r.stderr[-4000:])
    print(f"[dryrun] done: {len(cells) - len(failures)}/{len(cells)} OK")
    for f in failures:
        print("  FAILED:", f)
    return 1 if failures else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--remat", default="full", choices=["full", "none"])
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--json", help="write the cell result to this path")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--resume", action="store_true",
                    help="--all: skip cells with cached results")
    ap.add_argument("--timeout", type=int, default=3000)
    args = ap.parse_args()

    if args.all:
        return _run_all(args)
    if not args.arch or not args.shape:
        ap.error("--arch and --shape are required (or use --all)")

    out = run_cell(args.arch, args.shape, args.multi_pod, args.remat,
                   args.microbatch)
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)),
                    exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
