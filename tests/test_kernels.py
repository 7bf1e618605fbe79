"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(on the CPU backend the Pallas bodies run interpreted)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention, attention_ref
from repro.kernels.fleet_score import (median_lastdim_ref, score_rows,
                                       score_rows_ref)
from repro.kernels.sweep_burn import burn, burn_flops, burn_ref
from repro.kernels.wkv6 import wkv6, wkv6_naive

rng = np.random.RandomState(7)


def to_khw(x):
    return jnp.moveaxis(x, 1, 2)


class TestFlashAttention:
    @pytest.mark.parametrize("B,S,T,Hq,Hkv,hd,causal", [
        (2, 128, 128, 4, 2, 64, True),
        (1, 256, 256, 8, 8, 128, True),
        (2, 96, 96, 4, 1, 64, False),       # padding + MQA
        (1, 300, 300, 2, 2, 32, True),      # non-multiple lengths
        (2, 64, 192, 4, 2, 64, False),      # cross-shaped T != S
    ])
    def test_matches_oracle(self, B, S, T, Hq, Hkv, hd, causal):
        q = jnp.asarray(rng.randn(B, S, Hq, hd), jnp.float32)
        k = jnp.asarray(rng.randn(B, T, Hkv, hd), jnp.float32)
        v = jnp.asarray(rng.randn(B, T, Hkv, hd), jnp.float32)
        out = attention(q, k, v, causal=causal, block_q=64, block_k=64)
        ref = jnp.moveaxis(
            attention_ref(to_khw(q), to_khw(k), to_khw(v), causal=causal),
            1, 2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                            (jnp.bfloat16, 3e-2)])
    def test_dtypes(self, dtype, atol):
        q = jnp.asarray(rng.randn(1, 128, 4, 64), dtype)
        k = jnp.asarray(rng.randn(1, 128, 2, 64), dtype)
        v = jnp.asarray(rng.randn(1, 128, 2, 64), dtype)
        out = attention(q, k, v, block_q=64, block_k=64)
        ref = jnp.moveaxis(attention_ref(to_khw(q), to_khw(k), to_khw(v)),
                           1, 2)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=atol, rtol=atol)

    def test_grad_finite(self):
        q = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.float32)
        k = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.float32)
        v = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.float32)
        g = jax.grad(lambda q: attention(q, k, v, block_q=32,
                                         block_k=32).sum())(q)
        assert np.all(np.isfinite(np.asarray(g)))

    def test_block_shape_invariance(self):
        q = jnp.asarray(rng.randn(1, 256, 2, 64), jnp.float32)
        k = jnp.asarray(rng.randn(1, 256, 2, 64), jnp.float32)
        v = jnp.asarray(rng.randn(1, 256, 2, 64), jnp.float32)
        a = attention(q, k, v, block_q=64, block_k=64)
        b = attention(q, k, v, block_q=128, block_k=256)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


class TestWKV6:
    def _inputs(self, B, S, H, hd):
        mk = lambda *s: jnp.asarray(rng.randn(*s) * 0.5, jnp.float32)
        r, k, v = mk(B, S, H, hd), mk(B, S, H, hd), mk(B, S, H, hd)
        logw = -jnp.exp(jnp.asarray(rng.randn(B, S, H, hd) * 0.5 - 2.0,
                                    jnp.float32))
        u = mk(H, hd) * 0.3
        s0 = mk(B, H, hd, hd) * 0.1
        return r, k, v, logw, u, s0

    @pytest.mark.parametrize("B,S,H,hd,chunk", [
        (2, 128, 2, 64, 32),
        (1, 64, 4, 32, 64),
        (2, 96, 1, 16, 32),
        (1, 256, 2, 128, 64),
    ])
    def test_matches_both_oracles(self, B, S, H, hd, chunk):
        r, k, v, logw, u, s0 = self._inputs(B, S, H, hd)
        y, s = wkv6(r, k, v, logw, u, s0, chunk=chunk)
        yn, sn = wkv6_naive(to_khw(r), to_khw(k), to_khw(v), to_khw(logw),
                            u, s0)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(jnp.moveaxis(yn, 1, 2)),
                                   atol=2e-3, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(s), np.asarray(sn),
                                   atol=2e-3, rtol=1e-3)

    def test_chunk_invariance(self):
        r, k, v, logw, u, s0 = self._inputs(1, 128, 2, 32)
        y32, s32 = wkv6(r, k, v, logw, u, s0, chunk=32)
        y64, s64 = wkv6(r, k, v, logw, u, s0, chunk=64)
        np.testing.assert_allclose(np.asarray(y32), np.asarray(y64),
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(s32), np.asarray(s64),
                                   atol=1e-4)

    def test_state_carries_across_calls(self):
        """Processing 2*S tokens == two chained S-token calls."""
        r, k, v, logw, u, s0 = self._inputs(1, 128, 2, 32)
        y_full, s_full = wkv6(r, k, v, logw, u, s0, chunk=32)
        h = 64
        y1, s1 = wkv6(r[:, :h], k[:, :h], v[:, :h], logw[:, :h], u, s0,
                      chunk=32)
        y2, s2 = wkv6(r[:, h:], k[:, h:], v[:, h:], logw[:, h:], u, s1,
                      chunk=32)
        np.testing.assert_allclose(np.asarray(y_full[:, h:]),
                                   np.asarray(y2), atol=1e-3)
        np.testing.assert_allclose(np.asarray(s_full), np.asarray(s2),
                                   atol=1e-3)


class TestSweepBurn:
    @pytest.mark.parametrize("M,K,iters", [(128, 128, 16), (256, 256, 8),
                                           (512, 512, 16)])
    def test_matches_oracle(self, M, K, iters):
        a = jnp.asarray(rng.randn(M, K), jnp.float32)
        b = jnp.asarray(rng.randn(K, K), jnp.float32)
        out = burn(a, b, iters=iters, iters_per_block=8)
        ref = burn_ref(a, b, iters=iters)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-3)

    def test_flops_accounting(self):
        assert burn_flops(512, 512, 64) == 2 * 512**3 * 64

    def test_checksum_is_deterministic(self):
        a = jnp.asarray(rng.randn(128, 128), jnp.float32)
        b = jnp.asarray(rng.randn(128, 128), jnp.float32)
        o1 = burn(a, b, iters=16, iters_per_block=8)
        o2 = burn(a, b, iters=16, iters_per_block=8)
        assert np.array_equal(np.asarray(o1), np.asarray(o2))


class TestFleetScore:
    """Golden parity for repro.kernels.fleet_score: the jax and pallas
    backends must agree with the NumPy oracle (``score_rows_ref``)
    bit-for-bit on verdict masks — the detector's scalar-vs-batched
    contract rides on it."""

    def _mats(self, R, M, N):
        # tight healthy baseline (1.0-1.1) so the planted slowdowns are
        # unambiguous under the robust-z threshold for any seed draw
        mats = (rng.rand(R, M, N).astype(np.float32) * 0.1 + 1.0)
        mats[:, 0, N - 1] *= 1.5          # planted step-time straggler
        mats[:, 0, 3] *= 1.3
        return mats

    @pytest.mark.parametrize("n", [5, 8, 64, 129])
    def test_median_ref_matches_numpy(self, n):
        x = rng.rand(4, 3, n).astype(np.float32)
        got = median_lastdim_ref(x)
        want = np.median(x, axis=-1, keepdims=True).astype(np.float32)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("backend", ["jax", "pallas"])
    @pytest.mark.parametrize("R,M,N", [(3, 4, 64), (2, 3, 130)])
    def test_backends_match_ref(self, backend, R, M, N):
        mats = self._mats(R, M, N)
        dirs = [1.0, 1.0, -1.0, 1.0][:M]
        dev_r, rel_r, con_r = score_rows_ref(mats, dirs, 0)
        dev_b, rel_b, con_b = score_rows(mats, dirs, 0, backend=backend)
        np.testing.assert_array_equal(dev_r, dev_b)     # bit-identical
        np.testing.assert_allclose(rel_r, rel_b, rtol=0, atol=0)
        np.testing.assert_allclose(con_r, con_b, rtol=0, atol=0)

    def test_planted_straggler_flagged(self):
        mats = self._mats(2, 3, 32)
        dev, rel, contrib = score_rows_ref(mats, [1.0, 1.0, 1.0], 0)
        assert dev[:, 0, 31].all()
        assert (contrib[:, 31] > 0).all()
        assert contrib.shape == rel.shape == (2, 32)

    def test_numpy_backend_is_the_ref(self):
        mats = self._mats(2, 2, 16)
        a = score_rows(mats, [1.0, 1.0], 0, backend="numpy")
        b = score_rows_ref(mats, [1.0, 1.0], 0)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
