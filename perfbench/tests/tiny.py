"""Tiny configurations of the two drivers for tests on the CPU: the same
keys as the benchmark's configurations, at sizes a test run can hold."""
from __future__ import annotations

import copy
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def train_config(**over) -> dict:
    cfg = copy.deepcopy(load("configs", "phi3-mini-3.8b-4L.json"))
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=4, head_dim=16, num_hidden_layers=2,
               vocab_size=250, batch=2, seq_len=32, setup_steps=8,
               reference_query_block=16)
    # at widths of 64, bfloat16 rounding weighs more than at 3072: the
    # program's sound runs read up to 3e-4 / 1.8e-3 / 1.3e-3 here (CPU),
    # the float8 control 3.3e-3 / 3.8e-2 / 8.0e-3
    cfg["limits"].update(loss_rel=1e-3, grad_leaf_gap=6e-3,
                         change_leaf_gap=4e-3)
    cfg.update(over)
    return cfg


def fleet_config(**over) -> dict:
    cfg = copy.deepcopy(load("configs", "fleet-65k.json"))
    cfg.update(hosts=64, accelerators_per_host=8, frame_pool=8,
               sample_span=40, sample_frames=8)
    cfg["detector"]["scorer"] = "numpy"
    cfg.update(over)
    return cfg


def mix(name: str) -> dict:
    return load("mixes", name + ".json")
