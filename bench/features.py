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
import json

import jax
import jax.numpy as jnp
import numpy as np

from bench import cell as cells


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
    (``{"layers": [...], "head": {"w", "b"}}``), from ``seed``; each
    layer's from ``init`` of its ``bench/layers/<layer>.py``."""
    return _init(key_for(seed), json.dumps(model, sort_keys=True))


@functools.partial(jax.jit, static_argnums=1)
def _init(key, model_json: str):
    model = json.loads(model_json)
    layer = cells.load_layer(model["layer"])
    num_layers, hidden = model["num_layers"], model["hidden_dim"]
    keys = jax.random.split(key, num_layers + 1)
    layers = []
    d_in = model["feature_dim"]
    for i in range(num_layers):
        layers.append(layer.init(jax.random.split(keys[i], 3), d_in, hidden,
                                 model))
        d_in = hidden
    head = {"w": glorot(keys[-1], (hidden, model["classes"])),
            "b": jnp.zeros((model["classes"],), jnp.float32)}
    return {"layers": layers, "head": head}
