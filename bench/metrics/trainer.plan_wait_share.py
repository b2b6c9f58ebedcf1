"""Share of the traced window the Trainer's dispatch loop spent waiting
for the next plan (``plan.wait`` spans on the main thread): the host-side
reading of "host-bound". Less waiting raises ``roots_per_s``."""
LAYER = "trainer"
MOVES = "roots_per_s"
UNIT = "%"


def read(run):
    lo, hi = run.record["window_ns"]
    waits = [(max(s, lo), min(s + d, hi)) for n, _, s, d in run.record["host"]
             if n == "plan.wait"]
    if not waits:
        return None
    return 100.0 * sum(max(0.0, b - a) for a, b in waits) / (hi - lo)
