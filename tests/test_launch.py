"""The chip-facing entry points: the guarded training launcher, the compile
cache, the kernels' platform choice, state donation in the trainer, and
the dry-run's isolation from any chip."""
import json
import os
import subprocess
import sys
import tempfile

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.guard import GuardStepHook
from repro.kernels.backend import pallas_interpret
from repro.launch import compile_cache
from repro.launch.train import build_config, main, train
from repro.models.model import Model
from repro.train import (AdamWConfig, DataConfig, SyntheticLM,
                         TieredCheckpointManager, TrainConfig, Trainer)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _tiny():
    return reduced(get_config("phi3-mini-3.8b"), num_layers=2, d_model=128,
                   num_heads=4, num_kv_heads=4, d_ff=256, head_dim=32,
                   vocab_size=512)


class TestLauncher:
    def test_layers_keep_published_widths(self):
        full = get_config("phi3-mini-3.8b")
        cut = build_config("phi3-mini-3.8b", layers=4)
        assert cut.num_layers == 4
        assert (cut.d_model, cut.num_heads, cut.num_kv_heads, cut.head_dim,
                cut.d_ff, cut.vocab_size) == (
                    full.d_model, full.num_heads, full.num_kv_heads,
                    full.head_dim, full.d_ff, full.vocab_size)
        assert build_config("phi3-mini-3.8b", full=True) == full
        assert build_config("phi3-mini-3.8b").d_model < full.d_model

    def test_stall_is_detected_and_resumes_from_a_fast_tier(self):
        """The path chip_smoke drives, at a CPU size: the Guard hook flags
        an injected stall and the trainer rewinds to a fast snapshot."""
        hook = GuardStepHook(window_steps=4, n_peers=15, seed=0)
        hook.inject_stall(at_step=20, factor=8.0, steps=4)
        with tempfile.TemporaryDirectory() as d:
            out = train(_tiny(), steps=40, seq_len=32, batch=4, ckpt_dir=d,
                        ckpt_interval=41, lr=1e-3, hook=hook)
        losses = [h["loss"] for h in out["history"]]
        tiers = [e.ckpt_tier for e in hook.session.events()
                 if e.kind == "recovery"]
        assert out["final_step"] == 40
        assert hook.restarts_requested >= 1
        assert tiers and tiers[0] in ("peer", "local")
        assert len(out["history"]) > 40          # the rewind replayed steps
        assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]

    def test_main_runs_and_removes_its_temporary_checkpoints(
            self, monkeypatch, capsys):
        monkeypatch.setattr("repro.launch.train.enable_compile_cache",
                            lambda: "")
        made = []
        real = tempfile.mkdtemp
        monkeypatch.setattr(tempfile, "mkdtemp",
                            lambda **kw: made.append(real(**kw)) or made[-1])
        assert main(["--steps", "3", "--seq-len", "16", "--batch", "2"]) == 0
        assert "[train] done: 3 steps" in capsys.readouterr().out
        assert made and not os.path.exists(made[0])


class TestCompileCache:
    def test_env_directory_wins(self, monkeypatch):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(compile_cache.CACHE_ENV, "/somewhere/cache")
        assert compile_cache.enable_compile_cache() == "/somewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_at_the_checkout_root(self, monkeypatch):
        monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            got = compile_cache.enable_compile_cache()
            root = os.path.abspath(os.path.join(SRC, ".."))
            assert got == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert compile_cache.enable_compile_cache() == got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


def test_kernels_interpret_only_on_cpu():
    assert pallas_interpret() == (jax.default_backend() == "cpu")


class TestDonation:
    def _trainer(self, d, steps=4):
        cfg = _tiny()
        ckpt = TieredCheckpointManager(d, async_save=False,
                                       fast_interval_s=0.0)
        return Trainer(Model(cfg), SyntheticLM(DataConfig(cfg.vocab_size,
                                                          16, 2)),
                       TrainConfig(steps=steps, ckpt_interval=100,
                                   opt=AdamWConfig(warmup_steps=1,
                                                   total_steps=steps)),
                       ckpt=ckpt)

    def test_single_device_step_donates_state(self):
        with tempfile.TemporaryDirectory() as d:
            tr = self._trainer(d)
            old = jax.tree.leaves(tr.params)[0]
            batch = tr.data.batch_at(0)
            tr.params, tr.opt_state, _ = tr._step_fn(tr.params, tr.opt_state,
                                                     batch)
            assert old.is_deleted()

    def test_snapshot_survives_donation_and_repeated_rewinds(self):
        """A fast snapshot is a host copy: stepping on (donating the live
        buffers) and restoring from it twice gives the same state."""
        with tempfile.TemporaryDirectory() as d:
            tr = self._trainer(d)
            tr.run()
            snap = tr.ckpt.peer_step()
            want = [np.array(x) for x in
                    jax.tree.leaves(tr.ckpt.restore_any(
                        tr.params, tr.opt_state)[0])]
            for _ in range(2):
                assert tr.restore() == snap
                for a, b in zip(jax.tree.leaves(tr.params), want):
                    np.testing.assert_array_equal(np.asarray(a), b)
                batch = tr.data.batch_at(snap)
                tr.params, tr.opt_state, m = tr._step_fn(
                    tr.params, tr.opt_state, batch)
                assert np.isfinite(float(m["loss"]))


def _run_py(code: str, env_extra: dict) -> str:
    env = {**os.environ, "PYTHONPATH": SRC, **env_extra}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_mesh_trainer_places_params_with_their_shardings():
    """Under a (4, 1) mesh every parameter and moment is born with its
    own sharding, not whole on device 0 waiting for the first step."""
    got = _run_py(
        "import jax\n"
        "from repro.configs import get_config, reduced\n"
        "from repro.dist import api as dist\n"
        "from repro.launch.mesh import make_cpu_mesh\n"
        "from repro.models.model import Model\n"
        "from repro.train import DataConfig, SyntheticLM, TrainConfig, "
        "Trainer\n"
        "cfg = reduced(get_config('phi3-mini-3.8b'))\n"
        "model = Model(cfg)\n"
        "data = SyntheticLM(DataConfig(cfg.vocab_size, 16, 4))\n"
        "mesh = make_cpu_mesh()\n"
        "with mesh, dist.use_mesh(mesh) as ctx:\n"
        "    tr = Trainer(model, data, TrainConfig(steps=1))\n"
        "    want = jax.tree.leaves(dist.param_sharding(\n"
        "        model.param_axes(), model.param_shapes(), ctx))\n"
        "ok = all(p.sharding.is_equivalent_to(s, p.ndim) and\n"
        "         m.sharding.is_equivalent_to(s, m.ndim) for p, m, s in\n"
        "         zip(jax.tree.leaves(tr.params),\n"
        "             jax.tree.leaves(tr.opt_state['mu']), want))\n"
        "split = sum(len(p.sharding.device_set) == 4 and\n"
        "            p.addressable_shards[0].data.shape != p.shape\n"
        "            for p in jax.tree.leaves(tr.params))\n"
        "print(ok, split > 0)",
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
         "JAX_PLATFORMS": "cpu"})
    assert got == "True True"


class TestDryrunIsolation:
    def test_adds_its_device_count_and_pins_the_cpu(self):
        got = json.loads(_run_py(
            "import json, os, jax\n"
            "import repro.launch.dryrun\n"
            "print(json.dumps([os.environ['XLA_FLAGS'],"
            " jax.config.jax_platforms]))",
            {"XLA_FLAGS": "--xla_cpu_enable_fast_math=false "
                          "--xla_force_host_platform_device_count=8",
             "JAX_PLATFORMS": ""}))
        flags, platforms = got
        assert flags.split() == ["--xla_cpu_enable_fast_math=false",
                                 "--xla_force_host_platform_device_count=512"]
        assert platforms == "cpu"

    def test_all_parent_starts_no_backend(self, tmp_path):
        got = _run_py(
            "import argparse, subprocess\n"
            "import repro.launch.dryrun as d\n"
            "from jax._src import xla_bridge\n"
            f"d.RESULTS_DIR = {str(tmp_path)!r}\n"
            "d.subprocess.run = lambda cmd, **kw: "
            "subprocess.CompletedProcess(cmd, 0, '', '')\n"
            "rc = d._run_all(argparse.Namespace(resume=False, timeout=1))\n"
            "print(rc, xla_bridge.backends_are_initialized())",
            {"JAX_PLATFORMS": ""})
        assert got == "0 False"
