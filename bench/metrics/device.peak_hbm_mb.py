"""Peak device memory of the fullest chip after the window
(``memory_stats()["peak_bytes_in_use"]``): it bounds the batch that
fits, and with it ``roots_per_s``."""
LAYER = "device"
MOVES = "roots_per_s"
UNIT = "MB"


def read(run):
    return run.memory_peak_bytes / 1e6 if run.memory_peak_bytes else None
