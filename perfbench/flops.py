"""Operations and bytes that the work needs, from shapes alone.

These count what the algorithm requires, not what an implementation
happens to do, so a later change of algorithm is judged on the same
yardstick.
"""
from __future__ import annotations


def dense_lm_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs per token of a dense decoder LM.

    6 FLOPs per matmul parameter and token (2 forward, 4 backward),
    counting the LM head over the published vocabulary and not the
    embedding lookup; plus causal attention, which needs on average
    (S + 1) / 2 keys per query: QK^T and PV each 2 * hd FLOPs per key and
    head forward, times 3 for forward + backward. No recomputation counts.
    """
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    ff = cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    vocab = cfg["vocab_size"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    matmul_params = layers * per_layer + d * vocab
    attn = layers * 3 * 2 * 2 * h * hd * (seq_len + 1) / 2
    return 6.0 * matmul_params + attn


def fleet_score_work(rows: int, metrics: int, nodes: int) -> tuple:
    """(FLOPs, bytes) that scoring ``rows`` ring rows of ``metrics`` x
    ``nodes`` needs.

    Per metric row: two medians over the nodes (value, then absolute
    deviation) by linear-time selection, counted at 2 N comparisons
    each, and per element about 10 operations (difference, absolute
    value, scale, divide, direction, threshold, relative excess, floor
    test, masking). Bytes: the float32 rows read once, one verdict byte
    per element written, and the float32 relative excess and masked
    contribution of the step-time row written.
    """
    elems = rows * metrics * nodes
    flops = elems * (2 * 2 + 10)
    nbytes = elems * 4 + elems * 1 + 2 * rows * nodes * 4
    return float(flops), float(nbytes)
