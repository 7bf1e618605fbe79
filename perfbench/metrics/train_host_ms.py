"""Host milliseconds per step of the traced window in which
``Trainer.run`` has no step queued on the device: the program's spans
``train.batch`` + ``train.metrics`` + ``train.snapshot`` + ``train.hook``.
The caller's ``on_metrics`` is not the program's and is left out."""
from perfbench.program_record import totals

SPANS = ("train.batch", "train.metrics", "train.snapshot", "train.hook")


def read(result, cfg, device_kind):
    t = totals(spans=SPANS)
    if t is None:
        return None
    steps = result["counters"]["window_steps_completed"]
    return 1e3 * sum(t.values()) / max(steps, 1)
