"""Plain reference for LeapGNN training, and the comparison that decides
``correct``.

Imports nothing of the program. Its inputs are the benchmark's own: the
graph of :mod:`bench.graphgen`, the table and initial weights of
:mod:`bench.features`, and the traffic's roots and sampling seeds.

Semantics it reproduces, from the published description:

* Node-wise sampling with replacement to a fixed fanout, stateless: the
  ``j``-th neighbour of vertex ``v`` at hop ``h`` under seed ``s`` is
  ``N(v)[hash(v, j, h, s) mod deg(v)]`` with the SplitMix64 finaliser as
  the hash; a vertex without neighbours samples itself. The tree below a
  root is then a function of (root, seed) alone, so which worker trains a
  root at which time step cannot change it.
* The configuration's layer type, each layer's equations as
  ``bench/layers/<layer>.py`` states them (``apply``), applied hop by hop
  from the leaves up; a linear head; softmax cross-entropy averaged over
  all roots of the iteration.
* AdamW.

It runs the model in float32 at ``"highest"`` matmul precision, in blocks
of roots whose losses and gradients are summed, so it fits beside nothing
else on the chip. The configuration states bfloat16 matmul operands with
float32 accumulation (what the TPU does with float32 at its default
precision); ``control=True`` rounds every matmul operand to float8
(e4m3), the precision below, and gives the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import cell as cells
from bench.features import make_table

BLOCK_ROOTS = 256


# --------------------------------------------------------------------------
# Sampling
# --------------------------------------------------------------------------

def _splitmix64(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = x + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def sample_tree(indptr, indices, roots, num_layers: int, fanout: int,
                seed: int) -> list:
    """hops[0] = roots, hops[h + 1][i*f:(i+1)*f] = sampled neighbours of
    hops[h][i]."""
    hops = [np.asarray(roots, np.int64)]
    for h in range(num_layers):
        v = hops[-1]
        deg = indptr[v + 1] - indptr[v]
        with np.errstate(over="ignore"):
            key = (v.astype(np.uint64)[:, None] * np.uint64(0x100000001B3)
                   + np.arange(fanout, dtype=np.uint64)[None, :]
                   + np.uint64(h) * np.uint64(0x9E3779B9)
                   + np.uint64(seed) * np.uint64(0xDEADBEEF63))
        offs = (_splitmix64(key)
                % np.maximum(deg, 1).astype(np.uint64)[:, None])
        flat = (indptr[v][:, None] + offs.astype(np.int64)).reshape(-1)
        nbrs = indices[np.minimum(flat, indices.size - 1)].astype(np.int64)
        hops.append(np.where(np.repeat(deg == 0, fanout),
                             np.repeat(v, fanout), nbrs))
    return hops


# --------------------------------------------------------------------------
# Model
# --------------------------------------------------------------------------

def _fp8(x):
    """Round to float8 (e4m3) and back: the operand rounding of the
    control, one precision below the configuration's bfloat16 operands."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def forward(params, layer: str, fanout: int, feats, q=lambda x: x):
    """feats[h]: (B * fanout**h, d) -> logits (B, classes). Each layer is
    ``apply`` of ``bench/layers/<layer>.py``; ``q`` rounds every matmul
    operand."""
    apply = cells.load_layer(layer).apply
    hs = list(feats)
    for p in params["layers"]:
        hs = [apply(p, hs[h], hs[h + 1].reshape(hs[h].shape[0], fanout, -1),
                    q)
              for h in range(len(hs) - 1)]
    return q(hs[0]) @ q(params["head"]["w"]) + params["head"]["b"]


@functools.partial(jax.jit, static_argnames=("layer", "fanout", "control"))
def _block_loss_grad(params, table_flat, rows, masks, labels, *, layer,
                     fanout, control):
    def loss(p):
        feats = [jnp.take(table_flat, r, axis=0)
                 * (1.0 if m is None else m[:, None])
                 for r, m in zip(rows, masks)]
        logits = forward(p, layer, fanout, feats,
                         _fp8 if control else (lambda x: x))
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()

    return jax.value_and_grad(loss)(params)


class Reference:
    """The configuration's data on the device, ready for reference steps.
    Build it only after the program's state is freed."""

    def __init__(self, cfg: dict, graph, owner, local_idx, rows: int):
        m, g = cfg["model"], cfg["graph"]
        self.layer, self.fanout = m["layer"], int(m["fanout"])
        self.num_layers = int(m["num_layers"])
        self.graph = graph
        self.owner = owner
        table = make_table(g["data_seed"], graph.labels, owner, local_idx,
                           int(cfg["workers"]), rows, int(m["feature_dim"]),
                           int(g["classes"]))
        self.table_flat = table.reshape(-1, table.shape[-1])
        self.slot = (owner.astype(np.int64) * rows
                     + local_idx.astype(np.int64))

    def loss_grad(self, params, roots, seed: int, control: bool = False,
                  drop_remote: bool = False):
        """Mean loss and gradient over ``roots``, in blocks; float32 at
        ``"highest"`` precision, or the control's float8 operands."""
        hops = sample_tree(self.graph.indptr, self.graph.indices, roots,
                           self.num_layers, self.fanout, seed)
        home = self.owner[hops[0]]
        total, grads = 0.0, None
        f = self.fanout
        for a in range(0, len(roots), BLOCK_ROOTS):
            b = min(a + BLOCK_ROOTS, len(roots))
            ids = [hop[a * f ** h:b * f ** h] for h, hop in enumerate(hops)]
            rows = [jnp.asarray(self.slot[i].astype(np.int32)) for i in ids]
            masks = [None] * len(ids)
            if drop_remote:
                masks = [jnp.asarray(
                    (self.owner[i] == np.repeat(home[a:b], f ** h))
                    .astype(np.float32)) for h, i in enumerate(ids)]
            labels = jnp.asarray(self.graph.labels[ids[0]])
            with jax.default_matmul_precision("highest"):
                v, g = _block_loss_grad(params, self.table_flat, rows, masks,
                                        labels, layer=self.layer,
                                        fanout=self.fanout, control=control)
            total += float(v)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        n = float(len(roots))
        return total / n, jax.tree.map(lambda x: x / n, grads)


def adamw(opt: dict):
    """AdamW step ``(params, grads, state) -> (params, state)``."""
    lr, b1, b2 = float(opt["lr"]), float(opt["b1"]), float(opt["b2"])
    eps, wd = float(opt["eps"]), float(opt["weight_decay"])

    def init(params):
        z = jax.tree.map(jnp.zeros_like, params)
        return {"t": 0, "m": z, "v": z}

    def step(params, grads, state):
        t = state["t"] + 1
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                         state["v"], grads)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        params = jax.tree.map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                      + wd * p), params, m, v)
        return params, {"t": t, "m": m, "v": v}

    return init, step


FIRST_STEPS = 3
CHECKS = ("loss", "grad", "change")
READINGS = CHECKS + ("window_loss", "window_change")


def trajectory(ref: Reference, cfg: dict, params0, batches, *,
               control: bool = False, drop_remote: bool = False,
               keep: float = 1.0) -> dict:
    """The reference run over ``batches`` ((roots, seed) pairs): every
    step's loss, the first gradient, and the parameters after the first
    :data:`FIRST_STEPS` steps and after the last. ``keep`` < 1 trains on
    that leading share of each batch's roots (a fault: part of the batch
    left out)."""
    init, step = adamw(cfg["optimizer"])
    params, state = params0, init(params0)
    losses, first, params3 = [], None, None
    for i, (roots, seed) in enumerate(batches):
        roots = roots[:max(1, int(round(len(roots) * keep)))]
        loss, g = ref.loss_grad(params, roots, seed, control=control,
                                drop_remote=drop_remote)
        losses.append(loss)
        first = g if first is None else first
        params, state = step(params, g, state)
        if i + 1 == FIRST_STEPS:
            params3 = jax.device_get(params)
    return {"losses": losses, "grad": jax.device_get(first),
            "params3": params3, "params_end": jax.device_get(params)}


# --------------------------------------------------------------------------
# Comparison
# --------------------------------------------------------------------------

def _norms(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(np.linalg.norm(
        np.asarray(v, np.float64))) for k, v in flat}


def _worst_leaf(prog: dict, ref: dict, leaves) -> tuple:
    floor = float(np.median([ref[k] for k in leaves]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
            for k in leaves}
    gaps = {k: (v if np.isfinite(v) else np.inf) for k, v in gaps.items()}
    k = max(gaps, key=gaps.get)
    return gaps[k], k


def _minus(a, b) -> np.ndarray:
    return np.asarray(a, np.float64) - np.asarray(b, np.float64)


def _loss_gap(a: float, b: float) -> float:
    gap = abs(a - b) / abs(b)
    return gap if np.isfinite(gap) else np.inf


def compare(prog: dict, ref: dict, params0, window=None) -> dict:
    """The numbers compared, each against the reference run ``ref`` over
    the first steps of the program's run ``prog``:

    ``loss``: largest relative gap of a loss of the first three steps.
    ``grad``: worst leaf's gap between the norms of the first gradient,
    over the larger of that leaf's reference norm and the median leaf's.
    ``change``: the same for the parameters' change over the first three
    steps.

    Leaves whose reference gradient norm is under a thousandth of the
    median leaf's move under Adam by rounding alone; they are left out of
    the change and listed. Where ``ref`` covers the whole run and
    ``window`` gives the (first, end) steps of its window, two readings
    that are not compared come too: ``window_loss``, the largest relative
    loss gap in the window, and ``window_change``, as ``change`` over the
    whole run (see ``bench/control.py``)."""
    n = len(ref["losses"])
    if len(prog["losses"]) < n:
        raise ValueError(f"{len(prog['losses'])} program steps against "
                         f"{n} reference steps")
    gaps = [_loss_gap(a, b) for a, b in zip(prog["losses"], ref["losses"])]
    g_ref, g_prog = _norms(ref["grad"]), _norms(prog["grad"])
    grad, grad_leaf = _worst_leaf(g_prog, g_ref, list(g_ref))
    med = float(np.median(list(g_ref.values())))
    moving = [k for k, v in g_ref.items() if v >= 1e-3 * med]

    def change(key):
        moved = [_norms(jax.tree.map(_minus, r[key], params0))
                 for r in (prog, ref)]
        return _worst_leaf(*moved, moving)

    out = {"loss": max(gaps[:FIRST_STEPS]), "grad": grad,
           "grad_leaf": grad_leaf,
           "still_leaves": sorted(set(g_ref) - set(moving))}
    out["change"], out["change_leaf"] = change("params3")
    if window is not None:
        lo, hi = window
        out["window_loss"] = max(gaps[lo:hi])
        out["window_change"], out["window_change_leaf"] = change(
            "params_end")
    return out
