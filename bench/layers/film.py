"""GNN-FiLM (Brockschmidt, ICML 2020, arXiv:1906.12192, eq. 5), as the
configuration runs it: two edge types, the ``f`` sampled children and the
self loop, each with its own message weights and its own hypernetwork.
For a parent row ``h`` and children ``c_1 .. c_f``, with ``r`` in
{nbr, self}:

    [g_r | b_r] = h G_r + gb_r
    m_j = relu(g_nbr * (c_j W_nbr) + b_nbr),   j = 1 .. f
    m_0 = relu(g_self * (h W_self) + b_self)
    h'  = LayerNorm(mean_j m_j + m_0)

``W_nbr`` and ``W_self`` are ``d_in x d_out``, ``G_nbr`` and ``G_self``
``d_in x 2 d_out``, all Glorot-uniform; ``gb_r`` is zero; the LayerNorm's
scale is one and its shift zero, eps 1e-5. Departures from eq. (5), as in
the released implementation (microsoft/tf-gnn-samples, ``gnn_film``):
the mean over each edge type's messages in place of the sum, a one-layer
hypernetwork, the LayerNorm; no dropout. Every matmul operand passes
through ``q``; the LayerNorm runs in float32.

Training FLOPs of layer ``l`` (see ``bench/metrics/step.mfu.py``), on the
message-flow graph: ``W_nbr`` transforms each distinct child vertex once
(hops ``1 .. k-l``); each updated vertex (hops ``0 .. k-1-l``) runs both
hypernetworks (``2 d_in x 2 d_out``) and ``W_self``; the modulation of
each of its ``f + 1`` messages is ``2 d_out`` (product and shift), the
mean ``f d_out``. Backward: the matmuls count three times the forward,
twice in layer 0 (no input gradient); the modulation three times (the
gradients of the message and of ``g`` besides the forward), the mean
twice, in every layer: the hypernetworks' weights need them in layer 0
too. Activations and the LayerNorm are not counted.
"""
import jax
import jax.numpy as jnp

from bench.features import glorot

# Training FLOPs of a two-layer model (fanout 2, 3-d features, hidden 4,
# 5 classes) over 2, 3 and 4 distinct vertices at hops 0-2, by hand:
# layer 0 updates hops 0-1 (5 vertices) from children at hops 1-2 (7):
# W_nbr on 7 and W_self plus two 3x8 hypernetworks on 5 (2 * 3 * 4 flops
# per row per 3x4 block, x2), modulation of 5 x 3 messages of 4 (x3), mean
# over 2 children (x2); layer 1 updates hop 0 (2) from hop 1 (3): the same
# with 4x4 blocks (x3); head 2 roots x 4x5 (x3)
HAND_COUNT = (2 * 24 * (7 + 5 * 5) + 3 * 5 * 3 * 8 + 2 * 5 * 2 * 4
              + 3 * 32 * (3 + 5 * 2) + 3 * 2 * 3 * 8 + 2 * 2 * 2 * 4
              + 3 * 2 * 2 * 20)

LN_EPS = 1e-5


def init(keys, d_in: int, d_out: int, model: dict) -> dict:
    k1, k2, k3 = keys
    k_gnbr, k_gself = jax.random.split(k3)
    return {"w_nbr": glorot(k1, (d_in, d_out)),
            "w_self": glorot(k2, (d_in, d_out)),
            "film_nbr": glorot(k_gnbr, (d_in, 2 * d_out)),
            "film_nbr_b": jnp.zeros((2 * d_out,), jnp.float32),
            "film_self": glorot(k_gself, (d_in, 2 * d_out)),
            "film_self_b": jnp.zeros((2 * d_out,), jnp.float32),
            "ln_g": jnp.ones((d_out,), jnp.float32),
            "ln_b": jnp.zeros((d_out,), jnp.float32)}


def apply(p, parent, child, q):
    d = p["w_nbr"].shape[1]
    h = q(parent)
    g_nbr = h @ q(p["film_nbr"]) + p["film_nbr_b"]
    g_self = h @ q(p["film_self"]) + p["film_self_b"]
    m0 = jax.nn.relu(g_self[:, :d] * (h @ q(p["w_self"])) + g_self[:, d:])
    msg = q(child) @ q(p["w_nbr"])                        # (n, f, d)
    m = jax.nn.relu(g_nbr[:, None, :d] * msg + g_nbr[:, None, d:])
    x = (m.mean(axis=1) + m0).astype(jnp.float32)
    mu = x.mean(axis=-1, keepdims=True)
    var = jnp.square(x - mu).mean(axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["ln_g"] + p["ln_b"]


def train_flops(l: int, unique: list, k: int, f: int, d_in: int,
                d_out: int) -> float:
    dst = sum(unique[h] for h in range(k - l))
    src = sum(unique[h] for h in range(1, k - l + 1))
    mm_mult = 2.0 if l == 0 else 3.0
    return (mm_mult * 2 * d_in * d_out * (src + 5 * dst)
            + 3.0 * dst * (f + 1) * 2 * d_out
            + 2.0 * dst * f * d_out)
