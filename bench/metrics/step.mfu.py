"""Model FLOP/s utilisation of the whole training step: the operations
the forward and backward passes need on each worker's message-flow graph,
times iterations per second of the traced window, over the chips' bf16
peak.

The message-flow graph counts each (vertex, hop) once: the stateless
sampler gives a vertex the same children wherever it recurs at one hop,
so the tree layout's duplicate rows are recomputation and do not count.
Per worker's mini-batch, with ``U[h]`` the distinct vertices at hop ``h``
(``h = 0`` the roots, ``k`` layers, fanout ``f``), layer ``l`` (from the
input) updates hops ``0 .. k-1-l`` from hops ``0 .. k-l``; its count is
``train_flops`` of the layer type's ``bench/layers/<layer>.py``. The head
adds ``2 hidden classes`` per root.

Backward: matmuls count three times the forward (gradients of weights and
of inputs), except layer 0's, whose input is the feature table (twice: no
input gradient); each layer file states the rest of its own. Elementwise
ops (biases, activations, softmax) are not counted.
"""
import numpy as np

from bench import cell as cells
from bench.reference import sample_tree

LAYER = "step"
MOVES = "roots_per_s"
UNIT = "%"
SAMPLED_ITERATIONS = 4


def train_flops(unique: list, model: dict) -> float:
    """Training FLOPs of one worker's message-flow graph with ``unique[h]``
    distinct vertices at hop ``h``."""
    k, f = int(model["num_layers"]), int(model["fanout"])
    hidden = int(model["hidden_dim"])
    layer = cells.load_layer(model["layer"])
    total = 0.0
    d_in = int(model["feature_dim"])
    for l in range(k):
        total += layer.train_flops(l, unique, k, f, d_in, hidden)
        d_in = hidden
    total += 3.0 * unique[0] * 2 * hidden * int(model["classes"])
    return total


def iteration_flops(run, g: int) -> float:
    """Training FLOPs of iteration ``g``, summed over the workers."""
    model = run.cell["config"]["model"]
    seed = run.traffic.sample_seed(g)
    total = 0.0
    for roots in run.traffic.per_model(g):
        hops = sample_tree(run.graph.indptr, run.graph.indices, roots,
                           int(model["num_layers"]), int(model["fanout"]),
                           seed)
        total += train_flops([np.unique(h).size for h in hops], model)
    return total


def read(run):
    w = run.window
    its = w["iterations"][:SAMPLED_ITERATIONS]
    if not its or run.peak is None:
        return None
    per_iter = sum(iteration_flops(run, g) for g in its) / len(its)
    rate = per_iter * w["iters"] / w["seconds"]
    return 100.0 * rate / (run.chips * run.peak["bf16_flops_per_s"])
