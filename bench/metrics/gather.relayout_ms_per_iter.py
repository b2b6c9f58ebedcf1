"""Device time per window iteration of the feature gather outside its
kernel: the ops under the program's ``gather`` scope but not under
``gather/kernel`` (the workspace's pad to whole 128-lane words and its
reshape to ``(rows, 1, words)`` before the kernel, the index padding, the
slice back to the feature width after it), averaged over chips. Less
relayout raises ``roots_per_s`` where the device is the bound."""
from bench import scopes

LAYER = "kernels"
MOVES = "roots_per_s"
UNIT = "ms"


def read(run):
    if not any(scopes.scope_ns(run.record, "gather")) \
            or not run.window["iters"]:
        return None
    ns = scopes.scope_ns(run.record, "gather", exclude="gather/kernel")
    return sum(ns) / len(ns) / run.window["iters"] / 1e6
