"""GNN-FiLM (``models/gnn/layers.py`` ``film``) against a plain float32
reference of its published equations: forward, loss and gradients of the
tree model, the per-message activation, and training through the Trainer
(pre-gathering, the pipelined fused step) at depth 4."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.graph.sampler import sample_tree_block
from repro.models.gnn import GNNConfig, init_gnn
from repro.models.gnn.layers import film_apply
from repro.models.gnn.models import gnn_forward, gnn_loss
from repro.optim import adam
from repro.train import Trainer


def _ref_layer(p, parent, child):
    """One FiLM layer, message by message: for each edge type a one-layer
    hypernetwork gives (gamma, beta) from the target row, every message is
    modulated and activated on its own edge, the children's messages are
    averaged, the self message added, and a LayerNorm (eps 1e-5) closes."""
    d = p["w_nbr"].shape[1]
    g_nbr = parent @ p["film_nbr"] + p["film_nbr_b"]
    g_self = parent @ p["film_self"] + p["film_self_b"]
    f = child.shape[1]
    total = jnp.zeros((parent.shape[0], d), jnp.float32)
    for j in range(f):
        total = total + jnp.maximum(
            g_nbr[:, :d] * (child[:, j] @ p["w_nbr"]) + g_nbr[:, d:], 0.0)
    x = total / f + jnp.maximum(
        g_self[:, :d] * (parent @ p["w_self"]) + g_self[:, d:], 0.0)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * p["ln_g"] + p["ln_b"]


def _ref_logits(params, feats, fanout):
    hs = list(feats)
    for p in params["layers"]:
        hs = [_ref_layer(p, hs[h], hs[h + 1].reshape(hs[h].shape[0], fanout,
                                                     -1))
              for h in range(len(hs) - 1)]
    return hs[0] @ params["head"]["w"] + params["head"]["b"]


def _ref_loss(params, feats, labels, fanout):
    logp = jax.nn.log_softmax(_ref_logits(params, feats, fanout), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def _random_params(cfg, seed):
    """init_gnn's weights with every leaf, the zero biases and the
    LayerNorm's ones and zeros too, redrawn at random, so each term of the
    equations shows in the output."""
    params = init_gnn(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        0.5 * jax.random.normal(k, x.shape, jnp.float32)
        for k, x in zip(keys, leaves)])


CFG = GNNConfig(model="film", num_layers=3, hidden_dim=16, feature_dim=8,
                num_classes=5, fanout=3)
ROOTS = 6


def _feats(seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), CFG.num_layers + 1)
    return [jax.random.normal(keys[h], (ROOTS * CFG.fanout ** h, 8),
                              jnp.float32)
            for h in range(CFG.num_layers + 1)]


# Both sides compute in float32 at "highest" from the same operands; they
# differ only in the order of float32 sums (the reference adds the
# messages one by one, the layer reduces over an axis), a few ulps per
# layer over three layers: 1e-5 relative, 1e-6 absolute for entries near
# zero.
RTOL, ATOL = 1e-5, 1e-6


@pytest.mark.parametrize("seed", [0, 1])
def test_film_forward_matches_reference(seed):
    params = _random_params(CFG, seed)
    feats = _feats()
    with jax.default_matmul_precision("highest"):
        got = gnn_forward(params, CFG, feats)
        want = _ref_logits(params, feats, CFG.fanout)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)


def test_film_loss_and_gradients_match_reference():
    params = _random_params(CFG, 2)
    feats = _feats()
    labels = jnp.arange(ROOTS, dtype=jnp.int32) % CFG.num_classes
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(
            lambda p: gnn_loss(p, CFG, feats, labels), has_aux=True)(params)
        ref_loss, ref_g = jax.value_and_grad(_ref_loss)(params, feats,
                                                        labels, CFG.fanout)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=RTOL)
    assert jax.tree.structure(g) == jax.tree.structure(ref_g)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g)):
        # a gradient leaf's scale is set by the whole leaf: the tolerance
        # is relative to its largest entry
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=RTOL, atol=RTOL * scale)


def test_film_activation_is_per_message():
    """With a sign-changing modulation the ReLU of a mean is not the mean of
    ReLUs: taking the mean of the children before the transform (the
    shortcut a linear layer allows) gives another answer, and the layer
    gives the per-message one."""
    p = _random_params(CFG, 4)["layers"][1]
    keys = jax.random.split(jax.random.PRNGKey(9), 2)
    parent = jax.random.normal(keys[0], (32, 16), jnp.float32)
    child = jax.random.normal(keys[1], (32, 3, 16), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = film_apply(p, parent, child)
        want = _ref_layer(p, parent, child)
        # the shortcut: one message from the children's mean
        shortcut = _ref_layer(p, parent, child.mean(axis=1, keepdims=True))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)
    # LayerNorm outputs are O(1): a gap of 0.1 is far outside rounding
    assert float(jnp.abs(shortcut - want).max()) > 0.1


def test_film_matmuls_run_at_float32_precision():
    """Every matmul of the layer, forward and backward, asks for
    ``HIGHEST`` precision whatever the default, so the TPU does not round
    its operands to bfloat16."""
    p = _random_params(CFG, 4)["layers"][1]
    parent = jnp.ones((4, 16), jnp.float32)
    child = jnp.ones((4, 3, 16), jnp.float32)

    def loss(p, parent, child):
        return film_apply(p, parent, child).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(p, parent,
                                                               child)
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    # four forward matmuls, and two transposes of each in the backward
    assert len(dots) == 12
    for e in dots:
        assert e.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2, e


def test_film_trainer_depth4_matches_reference(partitioned):
    """Three iterations of a depth-4 FiLM model through the Trainer
    (hopgnn, pre-gathering, the pipelined fused step) read the losses of
    the reference trained on the same trees with the same optimizer."""
    d = partitioned
    ds = d["ds"]
    cfg = GNNConfig(model="film", num_layers=4, hidden_dim=16,
                    feature_dim=ds.feature_dim, num_classes=ds.num_classes,
                    fanout=2)
    tv = ds.train_vertices()
    roots = {it: [np.random.default_rng((it, m)).choice(tv, 6, replace=False)
                  for m in range(d["parts"])] for it in range(3)}
    base = 1234
    params0 = init_gnn(jax.random.PRNGKey(5), cfg)
    opt = adam(5e-3, key=("film-test",))
    trainer = Trainer(graph=ds.graph, labels=ds.labels, part=d["part"],
                      owner=d["owner"], local_idx=d["local_idx"],
                      table=d["table"], cfg=cfg, optimizer=opt,
                      params=params0, strategy="hopgnn", pregather=True,
                      pipeline=True, merging=False, sample_seed_base=base,
                      root_fn=lambda e, it: roots[it])
    stats = trainer.fit(epochs=1, iters_per_epoch=3, batch_per_model=6)
    got = list(stats[0].iter_losses)

    feats_all = jnp.asarray(ds.features, jnp.float32)
    params, state = params0, opt.init(params0)
    want = []
    for it in range(3):
        blocks = [sample_tree_block(ds.graph, r, cfg.num_layers, cfg.fanout,
                                    seed=base + it) for r in roots[it]]
        # the models' trees, concatenated hop by hop, are one tree of all
        # the iteration's roots
        feats = [feats_all[np.concatenate([b.hops[h] for b in blocks])]
                 for h in range(cfg.num_layers + 1)]
        labels = jnp.asarray(ds.labels[np.concatenate(roots[it])],
                             jnp.int32)
        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(_ref_loss)(params, feats, labels,
                                                    cfg.fanout)
        want.append(float(loss))
        params, state = opt.update(g, state, params)
    # float32 on both sides, in another order of sums; Adam's first steps
    # move every weight by about the learning rate whatever the gradient's
    # size, so a rounding-level gap in a gradient moves the next loss by
    # rounding only: 1e-4 relative over three steps
    np.testing.assert_allclose(got, want, rtol=1e-4)
