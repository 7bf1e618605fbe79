"""The on-chip benchmark of Guard: one cell, one run.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names a
configuration (``perfbench/configs/<name>.json``, which names its driver
under ``perfbench/drivers``) and a traffic mix
(``perfbench/mixes/<traffic>.json``); each per-layer metric is read by
``perfbench/metrics/<metric>.py``. With ``--trace 0`` the result carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
from a profiled run.

The run refuses to start without an accelerator, or with fewer chips
than the cell asks for. The last line of standard output is the JSON
result; the numbers compared to decide ``correct`` are printed beside
their limits as the last lines of standard error and under ``checks``,
the result's last key.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "perfbench")


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def cell_spec(bench: dict, name: str) -> tuple:
    """(cell, configuration, mix) of the cell ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return (cell, load_json(entry["file"]),
            load_json("perfbench", "mixes", cell["traffic"] + ".json"))


def metrics_of(bench: dict, cell: dict, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])
            or ("workloads" not in m and m["moves"] in names)]


def read_layer_metric(name: str, result: dict, cfg: dict, device_kind: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(result, cfg, device_kind)


def enable_cache() -> None:
    """JAX's persistent compilation cache at the program's fixed path
    inside the checkout (or ``JAX_COMPILATION_CACHE_DIR``), holding every
    program of the run, however short its compile."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("perfbench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    bench = load_json("BENCHMARK.json")
    cell, cfg, mix = cell_spec(bench, args.workload)

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"perfbench: platform {dev.platform}, device_kind "
          f"{dev.device_kind}, {len(devices)} device(s)", file=sys.stderr,
          flush=True)
    if dev.platform != "tpu" or len(devices) < cell["chips"]:
        print(f"perfbench: {args.workload} needs {cell['chips']} TPU "
              f"chip(s); found {len(devices)} {dev.platform} device(s)",
              file=sys.stderr)
        return 3
    enable_cache()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: compiles.append(time.perf_counter())
        if ev == "/jax/core/compile/backend_compile_duration" else None)

    from perfbench.spans import Spans
    driver = importlib.import_module("perfbench.drivers." + cfg["driver"])
    res = driver.run(cell, cfg, mix, args.seed, args.seconds,
                     bool(args.trace), T0, Spans())
    return report(bench, cell, cfg, res, dev, len(devices), bool(args.trace),
                  compiles)


def judge(cfg: dict, res: dict) -> tuple:
    """(correct, {number: {value, limit}}): every number compared is at
    or under the configuration's limit for it."""
    checks = {k: {"value": v, "limit": cfg["limits"][k]}
              for k, v in res["checks"].items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def report(bench, cell, cfg, res, dev, n_dev, trace, compiles=()) -> int:
    """Print the result line; exit code 0 whether or not it is correct."""
    correct, checks = judge(cfg, res)
    metrics = {}
    for m in metrics_of(bench, cell, trace):
        if trace:
            v = read_layer_metric(m["name"], res, cfg, dev.device_kind)
        else:
            v = res["e2e"].get(m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev,
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if trace and res["trace"]:
        device["busy_s"] = res["trace"]["busy_s"]
        device["window_s"] = res["trace"]["window_s"]
        out["breakdown"] = {"device_ops": res["trace"]["device_ops"],
                            "idle_gaps": res["trace"]["idle_gaps"]}
    out["checks"] = checks
    info = {"counters": res["counters"], "accuracy": res["accuracy"],
            "layer": {k: v for k, v in res["layer"].items()},
            "compiles_in_window": sum(
                res["window"][0] <= t <= res["window"][1] for t in compiles)}
    print("perfbench: " + json.dumps(info), file=sys.stderr)
    if res["accuracy"] is not None:
        print(f"perfbench: guard accuracy: false evictions "
              f"{res['accuracy']['false_evictions']}, misses "
              f"{res['accuracy']['misses']}", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
