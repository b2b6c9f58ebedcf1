"""Device time per window iteration of the §5.2 feature exchange: the
ops under the program's ``exchange`` scope (the emulated exchange's
``jnp.take``s, the all-to-alls on a mesh, and the ``[local | cached |
fetched]`` workspace concatenation), averaged over chips. A cheaper
exchange raises ``roots_per_s`` where the device is the bound."""
from bench import scopes

LAYER = "exchange"
MOVES = "roots_per_s"
UNIT = "ms"


def read(run):
    ns = scopes.scope_ns(run.record, "exchange")
    if not any(ns) or not run.window["iters"]:
        return None
    return sum(ns) / len(ns) / run.window["iters"] / 1e6
