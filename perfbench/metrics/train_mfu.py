"""Model FLOP utilization of the guarded job: the forward + backward
FLOPs its model needs per token (``perfbench/flops.py``, no recompute)
times the tokens per second the job keeps, over the chip's bf16 peak."""
from perfbench import peaks


def read(result, cfg, device_kind):
    layer = result["layer"]
    achieved = layer["train_flops_per_token"] * layer["goodput_tokens_per_s"]
    return 100.0 * achieved / peaks.peak(device_kind)["bf16_flops_per_s"]
