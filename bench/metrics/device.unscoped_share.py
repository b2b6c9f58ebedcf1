"""Share of the traced window's device op time (containers left out)
spent in ops under none of the program's scopes (``exchange``,
``gather``, ``layers``, ``update``): what the per-scope metrics cannot
place. A small share means they account for the device time that bounds
``roots_per_s``."""
from bench import scopes

LAYER = "device"
MOVES = "roots_per_s"
UNIT = "%"


def read(run):
    rec = run.record
    if not any(p for paths in scopes.device_scopes(rec) for p in paths):
        return None
    none, every = scopes.unscoped_ns(rec)
    return 100.0 * none / every if every else None
