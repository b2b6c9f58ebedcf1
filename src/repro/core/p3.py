"""Executable P³ baseline [Gandhi & Iyer, OSDI'21] — feature-dimension
model parallelism for the input layer, data parallelism above.

P³ hash-partitions the *feature dimension*: server p stores
``features[:, p·d/N:(p+1)·d/N]`` for every vertex, so raw features never
cross the network. The input layer runs model-parallel — each server
computes a partial first-layer output with its slice and the matching
*rows* of W₁, partials are summed across servers (one activation
all-reduce) — and the remaining layers run data-parallel on the (small)
hidden activations.

This module executes that schedule. Because a dim-sliced matmul summed over
slices equals the full matmul, P³'s gradients match model-centric training
to float tolerance — verified in tests (the same kind of placement-only
equivalence LeapGNN has). Supported models: gcn, sage, gat (input layer is
matmul-fronted). deepgcn normalizes *pre-matmul* over the full feature
vector, which P³'s slicing cannot express without an extra all-gather.
film multiplies two matmul outputs of the input layer (the FiLM scale
``h G`` times each message ``c W``), so every one of its four input
matmuls, the per-edge one included, would need its own partial-sum
reduction before the product; this module implements no such split.
Both are the paper's own "P³ suits particular architectures" caveat,
surfaced as ``P3Unsupported``.

Comm accounting mirrors core.comm_model.p3_bytes: hidden activations of
hops 0..k-1 cross the fabric (pull + gradient push), raw features never do.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.sampler import TreeBlock, sample_tree_block
from repro.graph.structs import CSRGraph
from repro.models.gnn.layers import LAYER_REGISTRY
from repro.models.gnn.models import GNNConfig

SUPPORTED = ("gcn", "sage", "gat")


class P3Unsupported(ValueError):
    pass


@dataclasses.dataclass
class P3Plan:
    """Per-model tree blocks with *global* vertex ids (P³ needs no
    owner/local mapping — every server holds every vertex's slice)."""

    blocks: list[TreeBlock]
    labels: list[np.ndarray]
    num_shards: int
    num_layers: int
    fanout: int
    hidden_dim: int

    def activation_bytes(self) -> int:
        """Hidden activations exchanged: hops 0..k-1 unique vertices per
        model, pull + push (×2), (N-1)/N remote share."""
        n = self.num_shards
        total = 0
        for blk in self.blocks:
            for h in range(self.num_layers):
                total += int(np.unique(blk.hops[h]).size)
        return int(2 * total * self.hidden_dim * 4 * (n - 1) / n)


def plan_p3(graph: CSRGraph, labels: np.ndarray,
            roots_per_model: Sequence[np.ndarray], num_layers: int,
            fanout: int, hidden_dim: int,
            sample_seed: int = 0) -> P3Plan:
    blocks, labs = [], []
    for roots in roots_per_model:
        roots = np.asarray(roots, np.int64)
        blocks.append(sample_tree_block(graph, roots, num_layers, fanout,
                                        seed=sample_seed))
        labs.append(labels[roots].astype(np.int32))
    return P3Plan(blocks=blocks, labels=labs,
                  num_shards=len(blocks), num_layers=num_layers,
                  fanout=fanout, hidden_dim=hidden_dim)


# ---------------------------------------------------------------------------
# dim-sliced first layer (the model-parallel piece)
# ---------------------------------------------------------------------------

def _first_layer_partial(model: str, p, parent_x, child_x, d_slice):
    """Partial pre-activation of layer 1 using feature dims ``d_slice``
    and the matching rows of W₁. Summing partials over slices == the full
    computation, so a psum finishes the layer."""
    px = parent_x[:, d_slice]
    cx = child_x[:, :, d_slice]
    if model == "gcn":
        f = cx.shape[1]
        agg = (px + jnp.sum(cx, axis=1)) / (f + 1.0)
        return agg @ p["w"][d_slice]                      # (n, d_out)
    if model == "sage":
        return (px @ p["w_self"][d_slice]
                + jnp.mean(cx, axis=1) @ p["w_nbr"][d_slice])
    if model == "gat":
        n, f, _ = cx.shape
        hp = px @ p["w"][d_slice]                         # (n, h*dh)
        hc = (cx.reshape(n * f, -1) @ p["w"][d_slice]).reshape(n, f, -1)
        return jnp.concatenate([hp[:, None], hc], axis=1)  # (n, 1+f, h*dh)
    raise P3Unsupported(model)


def _first_layer_finish(model: str, p, partial_sum, fanout):
    """Post-psum completion of layer 1 (bias, nonlinearity, attention)."""
    if model == "gcn":
        return jax.nn.relu(partial_sum + p["b"])
    if model == "sage":
        return jax.nn.relu(partial_sum + p["b"])
    if model == "gat":
        heads = p["a_src"].shape[0]
        n, f1, hd = partial_sum.shape
        dh = hd // heads
        hall = partial_sum.reshape(n, f1, heads, dh)
        hp, hc = hall[:, 0], hall[:, 1:]
        e_src = jnp.einsum("nhd,hd->nh", hp, p["a_src"])
        e_all = jnp.einsum("nfhd,hd->nfh", hall, p["a_dst"])
        logits = jax.nn.leaky_relu(e_src[:, None, :] + e_all, 0.2)
        alpha = jax.nn.softmax(logits, axis=1)
        out = jnp.einsum("nfh,nfhd->nhd", alpha, hall)
        return jax.nn.elu(out.reshape(n, heads * dh))
    raise P3Unsupported(model)


def _upper_layers(params, cfg: GNNConfig, h1_feats):
    """Layers 2..k data-parallel on hidden features (standard tree pass)."""
    _, apply_fn = LAYER_REGISTRY[cfg.model]
    f = cfg.fanout
    hs = list(h1_feats)
    for layer in range(1, cfg.num_layers):
        p = params["layers"][layer]
        new_hs = []
        for h in range(len(hs) - 1):
            parent = hs[h]
            d = hs[h + 1].shape[-1]
            child = hs[h + 1].reshape(parent.shape[0], f, d)
            new_hs.append(apply_fn(p, parent, child))
        hs = new_hs
    root = hs[0]
    return root @ params["head"]["w"] + params["head"]["b"]


def run_p3_iteration(params, features: jnp.ndarray, plan: P3Plan,
                     cfg: GNNConfig):
    """Execute one P³ iteration (emulated comm: the per-slice partial sums
    make the model-parallel schedule explicit; a shard_map realization
    replaces the python sum with lax.psum over the dim axis).

    Returns (grads, mean_loss) — gradient-parity-compatible with the
    model-centric engine."""
    if cfg.model not in SUPPORTED:
        raise P3Unsupported(
            f"{cfg.model}: P³'s input-layer slicing needs a matmul-fronted "
            f"layer (paper §1: P³ targets particular GNN shapes)")
    n = plan.num_shards
    d = cfg.feature_dim
    slices = [jnp.asarray(ix) for ix in
              np.array_split(np.arange(d), n)]
    total_roots = sum(len(l) for l in plan.labels)

    def loss_fn(prm):
        loss_sum = 0.0
        for s in range(n):
            blk = plan.blocks[s]
            k = cfg.num_layers
            # layer 1, model-parallel: partial per dim slice, then "psum"
            h1 = []
            for h in range(k):
                parent_x = jnp.asarray(features[blk.hops[h]])
                child_x = jnp.asarray(features[blk.hops[h + 1]]).reshape(
                    blk.hops[h].shape[0], cfg.fanout, d)
                partial = sum(
                    _first_layer_partial(cfg.model, prm["layers"][0],
                                         parent_x, child_x, sl)
                    for sl in slices)
                h1.append(_first_layer_finish(cfg.model, prm["layers"][0],
                                              partial, cfg.fanout))
            logits = _upper_layers(prm, cfg, h1)
            labs = jnp.asarray(plan.labels[s])
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp, labs[:, None].astype(jnp.int32), axis=-1)[:, 0]
            loss_sum = loss_sum + nll.sum()
        return loss_sum / total_roots

    loss, grads = jax.value_and_grad(lambda p: loss_fn(p))(params)
    return grads, loss
