"""The FLOP, byte and peak functions of the yardstick."""
import json
import os

import pytest

from perfbench import flops, peaks

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _phi3():
    with open(os.path.join(HERE, "configs", "phi3-mini-3.8b-4L.json")) as f:
        return json.load(f)


def test_phi3_flops_per_token_by_hand():
    cfg = _phi3()
    d, ff, v, s = 3072, 8192, 32064, 2048
    per_layer = 4 * d * d + 3 * d * ff          # q, k, v, o + SwiGLU
    matmul = 4 * per_layer + d * v               # 4 layers + LM head
    attn = 4 * 3 * 2 * 2 * d * (s + 1) / 2       # causal QK^T and PV
    assert flops.dense_lm_train_flops_per_token(cfg, s) == \
        pytest.approx(6 * matmul + attn, rel=1e-12)
    # about 3.46 GFLOP/token: 6 x 551.5 M matmul parameters + attention
    assert 3.4e9 < flops.dense_lm_train_flops_per_token(cfg, s) < 3.5e9


def test_fleet_score_work_counts_the_needed_work():
    f, b = flops.fleet_score_work(1, 8, 131072)
    elems = 8 * 131072
    assert f == 14 * elems
    assert b == 4 * elems + elems + 2 * 131072 * 4
    # two rows need twice the work
    assert flops.fleet_score_work(2, 8, 131072) == (2 * f, 2 * b)


def test_peaks_table_and_unknown_kind():
    p = peaks.peak("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("cpu")
