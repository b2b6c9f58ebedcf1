"""Share of the traced window in which no op ran on the chip: one minus
the union of the device-op intervals of the profiler trace over the
window, averaged over the chips. Less idle time raises ``roots_per_s``
where the device is not the bound."""
from bench import tracereduce

LAYER = "device"
MOVES = "roots_per_s"
UNIT = "%"


def read(run):
    rec = run.record
    busy = tracereduce.busy_ns(rec)
    if not any(busy):
        return None
    span = rec["window_ns"][1] - rec["window_ns"][0]
    return 100.0 * (1.0 - sum(busy) / len(busy) / span)
