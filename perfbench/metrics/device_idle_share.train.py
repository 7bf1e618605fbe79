"""Share of the traced window of the guarded job in which the device ran
no operation (profiler trace)."""
from perfbench.trace import idle_share_percent


def read(result, cfg, device_kind):
    return idle_share_percent(result["trace"])
