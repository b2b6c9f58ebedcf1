"""Feature bytes the §5.2 pre-gather exchange moves per trained root:
the program's exact count of deduplicated remote rows
(``EpochStats.remote_rows``) over the traced window, times the row width
in float32. A count that repeats exactly for a seed; fewer bytes shorten
the exchange and raise ``roots_per_s``."""
LAYER = "exchange"
MOVES = "roots_per_s"
UNIT = "kB/root"


def read(run):
    w = run.window
    roots = w["iters"] * run.traffic.batch
    if not roots:
        return None
    d = int(run.cell["config"]["model"]["feature_dim"])
    return w["remote_rows"] * d * 4 / 1e3 / roots
