"""End-to-end integration: the full Guard closed loop over a simulated
fleet, and the real-JAX sweep backend running the Pallas burn kernel."""
import numpy as np

from repro.core import SweepConfig, single_node_sweep
from repro.kernels.sweep_burn import LocalJaxSweepBackend
from repro.simcluster import FaultRates, RunConfig, Tier, simulate_run


class TestClosedLoopEndToEnd:
    def test_full_run_mitigates_injected_greys(self):
        """A run with a known grey population: Guard must remove most of
        the step-time inflation within the first simulated hours."""
        cfg = RunConfig(tier=Tier.ENHANCED, n_nodes=48, n_spare=8,
                        duration_h=8.0, initial_grey_p=0.25, seed=3)
        r = simulate_run(cfg)
        healthy = cfg.workload.healthy_step_s
        first_hour = r.step_times[: int(3600 / healthy)]
        last_hours = r.step_times[len(r.step_times) // 2:]
        assert np.mean(last_hours) < np.mean(first_hour)
        assert np.mean(last_hours) < healthy * 1.15

    def test_tier_ordering_on_mfu(self):
        mfus = {}
        for tier in Tier:
            r = simulate_run(RunConfig(tier=tier, n_nodes=48, n_spare=8,
                                       duration_h=10.0, initial_grey_p=0.2,
                                       seed=0))
            mfus[int(tier)] = r.mfu
        assert mfus[4] > mfus[1]
        assert mfus[3] > mfus[2] > mfus[1]

    def test_no_fault_run_is_clean(self):
        quiet = FaultRates(thermal=0, power=0, mem_ecc=0, nic_down=0,
                           nic_degraded=0, host_cpu=0, congestion=0,
                           fail_stop=0, admission_grey_p=0)
        r = simulate_run(RunConfig(tier=Tier.ENHANCED, n_nodes=32,
                                   n_spare=4, duration_h=4.0,
                                   initial_grey_p=0.0, rates=quiet, seed=1))
        assert r.crashes == 0
        assert r.guard_restarts == 0
        assert r.mfu > 0.19           # ~mfu_at_healthy


class TestLocalJaxBackend:
    def test_real_sweep_on_local_device(self):
        """The deployable path: the §5.2 sweep driving the actual Pallas
        burn kernel on this host's device."""
        backend = LocalJaxSweepBackend()
        ref = backend.reference()
        assert ref.device_tflops > 0
        rep = single_node_sweep(
            backend, node_id=0,
            cfg=SweepConfig(burn_seconds=8.0, compute_tolerance=0.5,
                            symmetry_tolerance=0.5, bw_tolerance=0.9),
        )
        assert rep.measurements["tflops"].shape[0] == \
            backend.device_count(0)
