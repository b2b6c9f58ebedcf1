"""Device time per window iteration of the feature gather's kernel: the
ops under the program's ``gather/kernel`` scope (the Pallas call of
``kernels.gather_agg.gather_rows``), averaged over chips. It follows the
scope, not the kernel's HLO text, so it still reads after the kernel is
replaced. A faster kernel raises ``roots_per_s`` where the device is the
bound."""
from bench import scopes

LAYER = "kernels"
MOVES = "roots_per_s"
UNIT = "ms"


def read(run):
    ns = scopes.scope_ns(run.record, "gather/kernel")
    if not any(ns) or not run.window["iters"]:
        return None
    return sum(ns) / len(ns) / run.window["iters"] / 1e6
