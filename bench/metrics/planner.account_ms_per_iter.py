"""Host time per plan built in the traced window in the planner's
``planner.account`` stage: the accounting over true roots that ends a
plan (it collects the true-root blocks and sums their feature rows; the
``np.unique`` dedup of the figures that need it runs on their first read,
off the training path). The program records the stage as a span on the
building thread, once per plan, nested in ``plan.build``; the four stages
split ``planner.ms_per_iter``. A faster stage raises ``roots_per_s``
where the host planner sets the pace."""
from bench import scopes

LAYER = "planner"
MOVES = "roots_per_s"
UNIT = "ms"


def read(run):
    ns = scopes.span_ns_per_build(run.record, "planner.account")
    return None if ns is None else ns / 1e6
