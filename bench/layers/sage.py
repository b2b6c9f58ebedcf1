"""GraphSAGE-mean (Hamilton et al., NeurIPS 2017), as the configuration
runs it. For a parent row ``h`` and its ``f`` sampled children ``c``:

    h' = relu(h W_self + mean(c) W_nbr + b)

``W_self`` and ``W_nbr`` are ``d_in x d_out``, Glorot-uniform; ``b`` is
zero. Every matmul operand passes through ``q``.

Training FLOPs of layer ``l`` (see ``bench/metrics/step.mfu.py``): two
``d_in x d_out`` matmuls per updated vertex, and the mean over its ``f``
children (``f * d_in`` adds). Backward: the matmuls count three times the
forward, twice in layer 0 (no input gradient); the means count twice, and
not at all in backward in layer 0.
"""
import jax
import jax.numpy as jnp

from bench.features import glorot

# Training FLOPs of a two-layer model (fanout 2, 3-d features, hidden 4,
# 5 classes) over 2, 3 and 4 distinct vertices at hops 0-2, by hand:
# layer 0 updates hops 0-1 (5 vertices): 2 matmuls of 3x4, fwd + weight
# grad (x2), means over 2 children of 3-d rows (fwd only); layer 1
# updates hop 0 (2): 2 matmuls of 4x4 (x3), means of 4-d rows (x2); head
# 2 roots x 4x5 (x3)
HAND_COUNT = (2 * 5 * 2 * 24 + 5 * 2 * 3
              + 3 * 2 * 2 * 32 + 2 * 2 * 2 * 4 + 3 * 2 * 2 * 20)


def init(keys, d_in: int, d_out: int, model: dict) -> dict:
    k1, k2, _ = keys
    return {"w_self": glorot(k1, (d_in, d_out)),
            "w_nbr": glorot(k2, (d_in, d_out)),
            "b": jnp.zeros((d_out,), jnp.float32)}


def apply(p, parent, child, q):
    return jax.nn.relu(q(parent) @ q(p["w_self"])
                       + q(child.mean(axis=1)) @ q(p["w_nbr"]) + p["b"])


def train_flops(l: int, unique: list, k: int, f: int, d_in: int,
                d_out: int) -> float:
    dst = sum(unique[h] for h in range(k - l))
    mm_mult = 2.0 if l == 0 else 3.0
    return (mm_mult * dst * 2 * (2 * d_in * d_out)
            + (1.0 if l == 0 else 2.0) * dst * f * d_in)
