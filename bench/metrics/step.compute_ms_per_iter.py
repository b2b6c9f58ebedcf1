"""Device time per window iteration of the model's own work: the ops
under the program's ``layers`` scope (the loss, forward and backward)
and its ``update`` scope (the gradient reduction and the optimizer
update), averaged over chips. Faster compute raises ``roots_per_s``
where the device is the bound."""
from bench import scopes

LAYER = "step"
MOVES = "roots_per_s"
UNIT = "ms"


def read(run):
    rec = run.record
    ns = [a + b for a, b in zip(scopes.scope_ns(rec, "layers"),
                                scopes.scope_ns(rec, "update"))]
    if not any(ns) or not run.window["iters"]:
        return None
    return sum(ns) / len(ns) / run.window["iters"] / 1e6
