"""Host milliseconds of ``StragglerDetector.update`` outside the
scorer's round trip, per frame of the traced window: the program's spans
``detector.push`` (ring push) + ``detector.score`` (gather into the
scorer's rows, write-back into the caches) - ``fleet_score.call`` +
``detector.verdicts`` (counts, slowdown, stall median, support, latch)."""
from perfbench.program_record import totals

SPANS = ("detector.push", "detector.score", "fleet_score.call",
         "detector.verdicts")


def read(result, cfg, device_kind):
    t = totals(spans=SPANS)
    if t is None:
        return None
    host = t["detector.push"] + t["detector.score"] - \
        t["fleet_score.call"] + t["detector.verdicts"]
    return 1e3 * host / result["layer"]["frames"]
