"""Offline node sweep on REAL hardware (this host), driven through the
``repro.guard`` control plane — the deployable path of §5.2.

The sweep hardware is the ``LocalJaxSweepBackend``: the MXU-aligned
sustained-matmul Pallas burn kernel (repro/kernels/sweep_burn) on the
local JAX device(s) plus pairwise bandwidth timing. Instead of wiring
``single_node_sweep`` by hand, the demo builds a NODE_SWEEP-tier
``GuardSession`` over that backend: the operator pulls the node for
verification (``replace_node``), the non-blocking scheduler runs the
sweep -> (if needed) triage -> sweep qualification loop, and every state
transition — quarantine, sweep start/finish, triage stages, the final
verdict — arrives as typed events on the session bus. The kernel is
compiled on a TPU host and interpreted on the CPU backend.

Run:  PYTHONPATH=src python examples/node_sweep_demo.py
"""

import jax

from repro.core.sweep import SweepConfig
from repro.core.triage import ErrorSignals
from repro.guard import GuardSession, LocalHostControl, SweepFinished
from repro.kernels.sweep_burn import LocalJaxSweepBackend, measure_tflops


class PrintSink:
    """Event-bus sink: every control-plane transition, as it happens."""

    def emit(self, ev) -> None:
        fields = {k: v for k, v in ev.to_dict().items()
                  if k not in ("kind", "t", "step") and v not in ("", ())}
        detail = ", ".join(f"{k}={v}" for k, v in fields.items())
        print(f"  [bus] {ev.kind:14s} {detail}")


def main():
    print("[sweep] building the NODE_SWEEP-tier session over the local "
          "JAX backend...")
    control = LocalHostControl()
    # the operator pulled this node for COMPUTE verification: a failed
    # sweep should walk the GPU remediation lane, not hit triage's
    # no-evidence early termination (which would RMA the host)
    control.signals_provider = lambda nid: ErrorSignals(
        gpu_errors=True, detail="operator-reported compute suspicion")
    backend = LocalJaxSweepBackend()
    ref = backend.reference()
    print(f"[sweep] reference: {ref.device_tflops:.3f} TFLOP/s "
          f"on {jax.default_backend()}, "
          f"{ref.intra_bw_gbps:.1f} GB/s")

    cfg = SweepConfig(burn_seconds=16.0, compute_tolerance=0.25,
                      symmetry_tolerance=0.25, bw_tolerance=0.8,
                      inflation_tolerance=2.0)
    session = GuardSession.node_sweep(control, backend, sweep_cfg=cfg)
    session.add_sink(PrintSink())
    session.register_active([0])
    session.register_spares([1])

    # the operator path: pull node 0 for offline verification; a spare
    # takes its place and the sweep scheduler picks it up event-driven
    print("[sweep] pulling node 0 for offline qualification...")
    session.replace_node(0, "operator-requested verification", step=0)
    control.t += 1.0
    session.advance(control.t)              # starts the queued sweep
    finish = session.scheduler.next_finish_t()
    control.t = (finish or control.t) + 1.0
    session.advance(control.t)              # lands the verdict

    done = [e for e in session.events() if isinstance(e, SweepFinished)]
    assert done, "qualification did not complete"
    verdict = done[-1]
    print(f"[sweep] node0 verdict: {verdict.outcome.upper()} "
          f"after {verdict.sweeps} sweep(s), "
          f"{verdict.duration_s:.0f}s simulated bench time")
    for f in verdict.failures:
        print("   failure:", f)
    print(f"[sweep] healthy spares now: {session.spare_ids()}")

    print("\n[sweep] sustained vs burst throughput (the §5.1 gap "
          "burn-in tests miss):")
    short = measure_tflops(iters=8, repeats=2)
    long = measure_tflops(iters=64, repeats=2)
    print(f"   8-iter burst: {short:.3f} TFLOP/s | "
          f"64-iter sustained: {long:.3f} TFLOP/s")


if __name__ == "__main__":
    main()
