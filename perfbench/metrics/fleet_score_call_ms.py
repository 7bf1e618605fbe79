"""Host milliseconds of the scorer's round trip per frame of the traced
window (the program's span ``fleet_score.call``: the staged rows to the
device, the kernel, the three outputs back as host arrays), beside the
kernel's own device time."""
from perfbench.program_record import totals


def read(result, cfg, device_kind):
    t = totals(spans=("fleet_score.call",))
    if t is None:
        return None
    return 1e3 * t["fleet_score.call"] / result["layer"]["frames"]
