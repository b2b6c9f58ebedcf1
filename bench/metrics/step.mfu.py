"""Model FLOP/s utilisation of the whole training step: the operations
the forward and backward passes need on each worker's message-flow graph,
times iterations per second of the traced window, over the chips' bf16
peak.

The message-flow graph counts each (vertex, hop) once: the stateless
sampler gives a vertex the same children wherever it recurs at one hop,
so the tree layout's duplicate rows are recomputation and do not count.
Per worker's mini-batch, with ``U[h]`` the distinct vertices at hop ``h``
(``h = 0`` the roots, ``k`` layers, fanout ``f``), layer ``l`` (from the
input) updates hops ``0 .. k-1-l`` from hops ``0 .. k-l``:

* GraphSAGE: two ``d_in x d_out`` matmuls per updated vertex, and the
  mean over its ``f`` children (``f * d_in`` adds).
* GAT: the projection of every vertex of hops ``0 .. k-l``, its
  ``a_dst`` logit (``2 d_out``), the ``a_src`` logit of every updated
  vertex (``2 d_out``) and its weighted sum over the self edge and ``f``
  children (``(f + 1) 2 d_out``).
* The head: ``2 hidden classes`` per root.

Backward: matmuls and GAT's attention count three times the forward
(gradients of weights and of inputs), except layer 0's matmuls, whose
input is the feature table (twice: no input gradient); SAGE's means count
twice, and not at all in backward in layer 0. Elementwise ops (biases,
activations, softmax) are not counted.
"""
import numpy as np

from bench.reference import sample_tree

LAYER = "step"
MOVES = "roots_per_s"
UNIT = "%"
SAMPLED_ITERATIONS = 4


def train_flops(unique: list, model: dict) -> float:
    """Training FLOPs of one worker's message-flow graph with ``unique[h]``
    distinct vertices at hop ``h``."""
    k, f = int(model["num_layers"]), int(model["fanout"])
    hidden, layer = int(model["hidden_dim"]), model["layer"]
    total = 0.0
    d_in = int(model["feature_dim"])
    for l in range(k):
        dst = sum(unique[h] for h in range(k - l))
        mm_mult = 2.0 if l == 0 else 3.0
        if layer == "sage":
            total += mm_mult * dst * 2 * (2 * d_in * hidden)
            total += (1.0 if l == 0 else 2.0) * dst * f * d_in
        elif layer == "gat":
            src = sum(unique[h] for h in range(k - l + 1))
            total += mm_mult * src * 2 * d_in * hidden
            total += 3.0 * (src * 2 * hidden
                            + dst * (2 * hidden + (f + 1) * 2 * hidden))
        else:
            raise ValueError(f"no FLOP count for layer {layer!r}")
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
