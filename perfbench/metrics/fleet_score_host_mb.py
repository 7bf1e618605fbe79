"""Megabytes that cross between host and device for the scorer per frame
of the traced window: the program's counters ``fleet_score.bytes_in``
(the staged rows) + ``fleet_score.bytes_out`` (the three outputs)."""
from perfbench.program_record import totals

COUNTERS = ("fleet_score.bytes_in", "fleet_score.bytes_out")


def read(result, cfg, device_kind):
    t = totals(counters=COUNTERS)
    if t is None:
        return None
    return sum(t.values()) / result["layer"]["frames"] / 1e6
