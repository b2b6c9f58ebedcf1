"""Feature table and initial weights, made on the device from seeds.

The table is the configuration's data: a function of its ``data_seed``,
laid out as the workers hold it, ``(shards, rows, d)`` float32 with zero
padding rows. Row ``local_idx[v]`` of shard ``owner[v]`` holds vertex
``v``: a standard normal vector plus half its class centre, so labels are
learnable from features. Both the program and the reference read this one
definition, never each other's copy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key_for(seed: int) -> jax.Array:
    """A PRNG key for any non-negative whole number, also past 2**32."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.key(seed & 0x7FFFFFFF)
    seed >>= 31
    while seed:
        key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
        seed >>= 31
    return key


@functools.partial(jax.jit, static_argnames=("rows", "dim", "classes"))
def _table(key, labels_by_slot, valid, *, rows: int, dim: int,
           classes: int):
    kc, kf = jax.random.split(key)
    centers = jax.random.normal(kc, (classes, dim), jnp.float32)

    def shard(k, lab, ok):
        x = jax.random.normal(k, (rows, dim), jnp.float32)
        return jnp.where(ok[:, None], x + 0.5 * centers[lab], 0.0)

    keys = jax.random.split(kf, labels_by_slot.shape[0])
    return jax.vmap(shard)(keys, labels_by_slot, valid)


def make_table(data_seed: int, labels: np.ndarray, owner: np.ndarray,
               local_idx: np.ndarray, shards: int, rows: int, dim: int,
               classes: int) -> jax.Array:
    """The ``(shards, rows, dim)`` float32 feature table on the default
    device, in one jitted call."""
    lab = np.zeros((shards, rows), np.int32)
    ok = np.zeros((shards, rows), bool)
    lab[owner, local_idx] = labels
    ok[owner, local_idx] = True
    return _table(key_for(data_seed), lab, ok, rows=rows, dim=dim,
                  classes=classes)


def glorot(key, shape):
    lim = float(np.sqrt(6.0 / (shape[-2] + shape[-1])))
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def init_params(seed: int, model: dict) -> dict:
    """Initial weights in the program's parameter layout
    (``{"layers": [...], "head": {"w", "b"}}``), from ``seed``."""
    return _init(key_for(seed), model["layer"], model["num_layers"],
                 model["feature_dim"], model["hidden_dim"],
                 model["classes"], model.get("heads", 1))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _init(key, layer, num_layers, feature_dim, hidden, classes, heads):
    keys = jax.random.split(key, num_layers + 1)
    layers = []
    d_in = feature_dim
    for i in range(num_layers):
        k1, k2, k3 = jax.random.split(keys[i], 3)
        if layer == "sage":
            layers.append({"w_self": glorot(k1, (d_in, hidden)),
                           "w_nbr": glorot(k2, (d_in, hidden)),
                           "b": jnp.zeros((hidden,), jnp.float32)})
        elif layer == "gat":
            dh = hidden // heads
            layers.append({
                "w": glorot(k1, (d_in, hidden)),
                "a_src": 0.1 * jax.random.normal(k2, (heads, dh)),
                "a_dst": 0.1 * jax.random.normal(k3, (heads, dh))})
        else:
            raise ValueError(f"unknown layer {layer!r}")
        d_in = hidden
    head = {"w": glorot(keys[-1], (hidden, classes)),
            "b": jnp.zeros((classes,), jnp.float32)}
    return {"layers": layers, "head": head}
