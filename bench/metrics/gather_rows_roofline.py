"""Share of the HBM roofline reached by the feature gather: the logical
bytes it moves (every row it returns, read once and written once, at the
feature width in float32) over its device time in the trace times the
chips' HBM bandwidth. Padded lanes and DMA descriptors are not counted,
so the number reads the same work whatever implements the gather. A
faster gather raises ``roots_per_s`` where the device is the bound.

Rows per iteration: every worker gathers, for each of its time steps,
``batch_pad * fanout**h`` rows at hop ``h = 0 .. k``.
"""
from bench import tracereduce

LAYER = "kernels"
MOVES = "roots_per_s"
UNIT = "%"
# Trace ops of the feature gather, named by their HLO text. The Pallas
# ``gather_rows`` kernel is a custom call from an s32 index vector to
# (rows, 1, lanes) float32 rows; ``lax.platform_dependent`` names it
# ``branch_0_fun``.
ACCEPT = r"= f32\[\d+,1,\d+\]\{[^}]*\} custom-call\(s32\["


def bytes_per_iteration(workers: int, steps: int, batch_pad: int,
                        model: dict) -> float:
    f, k = int(model["fanout"]), int(model["num_layers"])
    rows = workers * steps * batch_pad * sum(f ** h for h in range(k + 1))
    return 2.0 * rows * int(model["feature_dim"]) * 4


def read(run):
    if run.peak is None or not run.window["iters"]:
        return None
    kernel_ns = sum(tracereduce.op_ns(run.record, ACCEPT))
    if not kernel_ns:
        return None
    model = run.cell["config"]["model"]
    moved = run.window["iters"] * bytes_per_iteration(
        run.workers, run.merge_steps, run.batch_pad, model)
    return 100.0 * moved / (kernel_ns / 1e9 * run.peak["hbm_bytes_per_s"])
