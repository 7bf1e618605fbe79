"""Share of the ``fleet_score`` kernel's roofline: the least time the
chip needs for the scoring's work (``perfbench/flops.py``: the frame
rows read, the verdicts written, O(N) order statistics per metric row),
the larger of its FLOP bound and its byte bound, over the kernel's
device time per frame. One new ring row is scored per frame."""
from perfbench import flops, peaks


def read(result, cfg, device_kind):
    tr = result["trace"]
    if not tr or not tr["kernel_events"].get("fleet_score"):
        return None
    layer = result["layer"]
    work, nbytes = flops.fleet_score_work(1, layer["metrics"], layer["rows"])
    pk = peaks.peak(device_kind)
    least = max(work / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    per_frame = tr["kernel_s"]["fleet_score"] / layer["frames"]
    return 100.0 * least / per_frame
