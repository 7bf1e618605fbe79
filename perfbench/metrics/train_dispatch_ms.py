"""Host milliseconds of the jitted step's call per step of the traced
window (the program's span ``train.dispatch``, inside ``Trainer.run``)."""
from perfbench.program_record import totals


def read(result, cfg, device_kind):
    t = totals(spans=("train.dispatch",))
    if t is None:
        return None
    steps = result["counters"]["window_steps_completed"]
    return 1e3 * t["train.dispatch"] / max(steps, 1)
