"""GAT (Velickovic et al., ICLR 2018), as the configuration runs it: a
self edge beside the ``f`` sampled children, ``heads`` heads of width
``d_out / heads``, concatenated. For a parent row ``h`` and children
``c_1 .. c_f``, with ``v_0 = h W`` and ``v_j = c_j W``, per head:

    e_j = LeakyReLU_0.2(a_src . (h W) + a_dst . v_j),  j = 0 .. f
    h'  = ELU(sum_j softmax_j(e) v_j)

``W`` is ``d_in x d_out``, Glorot-uniform; ``a_src`` and ``a_dst`` are
``heads x d_out / heads``, normal with standard deviation 0.1. The
configuration's ``model.heads`` gives the heads (1 where it is absent).
Every matmul operand passes through ``q``.

Training FLOPs of layer ``l`` (see ``bench/metrics/step.mfu.py``): the
projection of every vertex of hops ``0 .. k-l``, its ``a_dst`` logit
(``2 d_out``), the ``a_src`` logit of every updated vertex (``2 d_out``)
and its weighted sum over the self edge and ``f`` children
(``(f + 1) 2 d_out``). Backward: the projection counts three times the
forward, twice in layer 0 (no input gradient); the attention three
times.
"""
import jax
import jax.numpy as jnp

from bench.features import glorot

# Training FLOPs of a two-layer model (fanout 2, 3-d features, hidden 4,
# 5 classes) over 2, 3 and 4 distinct vertices at hops 0-2, by hand:
# layer 0 projects hops 0-2 (9 vertices, 3x4, x2), attention over 9
# sources and 5 targets with 2 children each (x3); layer 1 projects hops
# 0-1 (5, 4x4, x3), attention over 5 sources and 2 targets (x3); head 2
# roots x 4x5 (x3)
HAND_COUNT = (2 * 9 * 24 + 3 * (9 * 8 + 5 * (8 + 3 * 8))
              + 3 * 5 * 32 + 3 * (5 * 8 + 2 * (8 + 3 * 8)) + 3 * 2 * 2 * 20)


def init(keys, d_in: int, d_out: int, model: dict) -> dict:
    k1, k2, k3 = keys
    heads = model.get("heads", 1)
    dh = d_out // heads
    return {"w": glorot(k1, (d_in, d_out)),
            "a_src": 0.1 * jax.random.normal(k2, (heads, dh)),
            "a_dst": 0.1 * jax.random.normal(k3, (heads, dh))}


def apply(p, parent, child, q):
    heads, dh = p["a_src"].shape
    n, f, _ = child.shape
    w = q(p["w"])
    hp = (q(parent) @ w).reshape(n, heads, dh)
    hc = (q(child) @ w).reshape(n, f, heads, dh)
    vals = jnp.concatenate([hp[:, None], hc], axis=1)      # (n, f+1, h, dh)
    src = jnp.einsum("nhd,hd->nh", q(hp), q(p["a_src"]))
    dst = jnp.einsum("nfhd,hd->nfh", q(vals), q(p["a_dst"]))
    alpha = jax.nn.softmax(jax.nn.leaky_relu(src[:, None] + dst, 0.2), axis=1)
    return jax.nn.elu(jnp.einsum("nfh,nfhd->nhd", q(alpha), q(vals))
                      .reshape(n, heads * dh))


def train_flops(l: int, unique: list, k: int, f: int, d_in: int,
                d_out: int) -> float:
    dst = sum(unique[h] for h in range(k - l))
    mm_mult = 2.0 if l == 0 else 3.0
    src = sum(unique[h] for h in range(k - l + 1))
    return (mm_mult * src * 2 * d_in * d_out
            + 3.0 * (src * 2 * d_out
                     + dst * (2 * d_out + (f + 1) * 2 * d_out)))
