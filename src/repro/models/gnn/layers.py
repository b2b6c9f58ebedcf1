"""GNN layer primitives over tree blocks.

A *tree level pair* is ``(parent, child)`` with shapes
``parent: (n, d_in)``, ``child: (n, f, d_in)`` — children of parent i are
``child[i]``. Every layer maps this pair to updated parent embeddings
``(n, d_out)``.

The neighbor aggregation (`gather + reduce` — DGL's SpMM, the compute
hot-spot the paper's domain optimizes) is injectable so the Pallas kernel in
:mod:`repro.kernels.gather_agg` can replace the jnp reference on TPU.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.scopes import FILM_EDGES, FILM_NODES


def glorot(key, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[-2], shape[-1]
    lim = float(np.sqrt(6.0 / (fan_in + fan_out)))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


# ---------------------------------------------------------------------------
# Aggregators (child: (n, f, d) -> (n, d))
# ---------------------------------------------------------------------------

def agg_mean(child: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean(child, axis=1)


def agg_sum(child: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(child, axis=1)


def agg_max(child: jnp.ndarray) -> jnp.ndarray:
    return jnp.max(child, axis=1)


AGGREGATORS = {"mean": agg_mean, "sum": agg_sum, "max": agg_max}


# ---------------------------------------------------------------------------
# Layers. Each layer is (init_fn, apply_fn) over (parent, child).
# ---------------------------------------------------------------------------

def gcn_init(key, d_in, d_out):
    return {"w": glorot(key, (d_in, d_out)), "b": jnp.zeros((d_out,))}


def gcn_apply(p, parent, child, act=jax.nn.relu):
    """Kipf-Welling GCN with mean normalization (self + neighbors)."""
    f = child.shape[1]
    agg = (parent + jnp.sum(child, axis=1)) / (f + 1.0)
    return act(agg @ p["w"] + p["b"])


def sage_init(key, d_in, d_out):
    k1, k2 = jax.random.split(key)
    return {"w_self": glorot(k1, (d_in, d_out)),
            "w_nbr": glorot(k2, (d_in, d_out)),
            "b": jnp.zeros((d_out,))}


def sage_apply(p, parent, child, act=jax.nn.relu):
    """GraphSAGE-mean: act(W_s h_v + W_n mean(h_N(v)))."""
    return act(parent @ p["w_self"] + agg_mean(child) @ p["w_nbr"] + p["b"])


def gat_init(key, d_in, d_out, heads=4):
    assert d_out % heads == 0
    k1, k2, k3 = jax.random.split(key, 3)
    dh = d_out // heads
    return {"w": glorot(k1, (d_in, heads * dh)),
            "a_src": 0.1 * jax.random.normal(k2, (heads, dh)),
            "a_dst": 0.1 * jax.random.normal(k3, (heads, dh))}


def gat_apply(p, parent, child, act=jax.nn.elu):
    """GAT: softmax(LeakyReLU(a^T[Wh_i || Wh_j])) attention over sampled
    neighbors (incl. self edge, as DGL does with add_self_loop)."""
    heads = p["a_src"].shape[0]  # heads inferred from attention params
    n, f, d_in = child.shape
    dh = p["w"].shape[1] // heads
    hp = (parent @ p["w"]).reshape(n, heads, dh)
    hc = (child @ p["w"]).reshape(n, f, heads, dh)
    # attention logits: e_ij = leaky(a_src . h_i + a_dst . h_j)
    e_src = jnp.einsum("nhd,hd->nh", hp, p["a_src"])            # (n, h)
    e_dst = jnp.einsum("nfhd,hd->nfh", hc, p["a_dst"])          # (n, f, h)
    e_self = jax.nn.leaky_relu(e_src + jnp.einsum("nhd,hd->nh", hp, p["a_dst"]), 0.2)
    e = jax.nn.leaky_relu(e_src[:, None, :] + e_dst, 0.2)       # (n, f, h)
    logits = jnp.concatenate([e_self[:, None, :], e], axis=1)    # (n, f+1, h)
    alpha = jax.nn.softmax(logits, axis=1)
    vals = jnp.concatenate([hp[:, None], hc], axis=1)            # (n, f+1, h, dh)
    out = jnp.einsum("nfh,nfhd->nhd", alpha, vals).reshape(n, heads * dh)
    return act(out)


def deepgcn_init(key, d_in, d_out):
    # ResGCN+ block: pre-norm, GCN aggregation, residual.
    k1, _ = jax.random.split(key)
    return {"w": glorot(k1, (d_in, d_out)), "b": jnp.zeros((d_out,)),
            "ln_g": jnp.ones((d_in,)), "ln_b": jnp.zeros((d_in,))}


def _layernorm(x, g, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def deepgcn_apply(p, parent, child, act=jax.nn.relu):
    """DeepGCN (ResGCN+): h + W·act(LN(mean-agg)). Residual requires
    d_in == d_out (enforced by the model builder for hidden layers)."""
    f = child.shape[1]
    agg = (parent + jnp.sum(child, axis=1)) / (f + 1.0)
    y = act(_layernorm(agg, p["ln_g"], p["ln_b"])) @ p["w"] + p["b"]
    return parent + y if parent.shape[-1] == y.shape[-1] else y


FILM_PRECISION = jax.lax.Precision.HIGHEST


def film_init(key, d_in, d_out):
    k_nbr, k_self, k_gnbr, k_gself = jax.random.split(key, 4)
    return {"w_nbr": glorot(k_nbr, (d_in, d_out)),
            "w_self": glorot(k_self, (d_in, d_out)),
            "film_nbr": glorot(k_gnbr, (d_in, 2 * d_out)),
            "film_nbr_b": jnp.zeros((2 * d_out,)),
            "film_self": glorot(k_gself, (d_in, 2 * d_out)),
            "film_self_b": jnp.zeros((2 * d_out,)),
            "ln_g": jnp.ones((d_out,)), "ln_b": jnp.zeros((d_out,))}


def film_apply(p, parent, child, act=jax.nn.relu):
    """GNN-FiLM (Brockschmidt, ICML 2020, eq. 5), with the self loop as an
    edge type of its own. For each edge type r in {nbr, self}, the target
    row h computes its modulation with a hypernetwork,
    ``[γ_r | β_r] = h G_r + g_r``, and every message is modulated and
    activated on its own edge:

        m_j = act(γ_nbr ⊙ (c_j W_nbr) + β_nbr)     j = 1 … f
        m_0 = act(γ_self ⊙ (h W_self) + β_self)
        h'  = LayerNorm((1/f) Σ_j m_j + m_0)

    The activation is per message, so the mean over the children comes
    after it; no linear shortcut takes the mean before ``W_nbr``.

    Departures from eq. (5), as the released implementation
    (microsoft/tf-gnn-samples, ``gnn_film``) has them:

    - each edge type's messages are averaged, not summed (its
      ``normalize_by_num_incoming``); a fixed-fanout sample estimates
      the neighbourhood sum only up to the factor ``f`` anyway;
    - the hypernetwork is one linear layer (its default);
    - the LayerNorm over the result comes from it and is not in eq. (5);
    - no dropout, so training stays deterministic.

    The four matmuls run at float32 precision (``FILM_PRECISION``), not
    the TPU's default single bfloat16 pass. Each layer multiplies two
    matmul outputs, so a perturbation of its input grows by about 1.45x
    per layer: after 10 layers bfloat16 operands leave about 12% relative
    error in the root rows and 45-50% in the first gradient of most
    weights, so the gradient no longer matches model-centric training's
    (§5.1) beyond rounding.
    """
    d_out = p["w_nbr"].shape[1]
    mm = functools.partial(jnp.matmul, precision=FILM_PRECISION)
    with jax.named_scope(FILM_NODES):
        gb_nbr = mm(parent, p["film_nbr"]) + p["film_nbr_b"]       # (n, 2d)
        gb_self = mm(parent, p["film_self"]) + p["film_self_b"]
        m0 = act(gb_self[:, :d_out] * mm(parent, p["w_self"])
                 + gb_self[:, d_out:])
    with jax.named_scope(FILM_EDGES):
        msg = mm(child, p["w_nbr"])                               # (n, f, d)
        m = act(gb_nbr[:, None, :d_out] * msg + gb_nbr[:, None, d_out:])
        agg = jnp.mean(m, axis=1)
    with jax.named_scope(FILM_NODES):
        return _layernorm(agg + m0, p["ln_g"], p["ln_b"])


LAYER_REGISTRY: dict[str, tuple[Callable, Callable]] = {
    "gcn": (gcn_init, gcn_apply),
    "sage": (sage_init, sage_apply),
    "gat": (gat_init, gat_apply),
    "deepgcn": (deepgcn_init, deepgcn_apply),
    "film": (film_init, film_apply),
}
