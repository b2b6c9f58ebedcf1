"""Layer types as files of their own (``bench/layers/<layer>.py``): the
initial weights, reference logits and FLOP counts of ``sage`` and ``gat``
equal, bit for bit, what the harness gave before they moved there; and a
layer type the benchmark has never seen runs a cell through ``run_cell``
from one new file, with no harness file edited."""
import copy
import hashlib
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cell as cells  # noqa: E402
from bench import reference  # noqa: E402
from bench.features import init_params, key_for  # noqa: E402

SEED = 2 ** 33 + 5
SMALL = {"num_layers": 2, "fanout": 2, "feature_dim": 3, "hidden_dim": 4,
         "classes": 5, "heads": 2}

# sha256 (first 16 hex digits) of each leaf's float32 bytes, from the
# harness before the layer types moved into bench/layers/
INIT = {
    "sage": {
        "['head']['b']": "de47c9b27eb8d300",
        "['head']['w']": "555b6fc0f70fdf92",
        "['layers'][0]['b']": "374708fff7719dd5",
        "['layers'][0]['w_nbr']": "ce0d25369932bd83",
        "['layers'][0]['w_self']": "d69f9c5ff84bdc3b",
        "['layers'][1]['b']": "374708fff7719dd5",
        "['layers'][1]['w_nbr']": "de3607e83ac7e7dd",
        "['layers'][1]['w_self']": "1c08119aee899e76"},
    "gat": {
        "['head']['b']": "de47c9b27eb8d300",
        "['head']['w']": "555b6fc0f70fdf92",
        "['layers'][0]['a_dst']": "eb80448b5bd7bbdc",
        "['layers'][0]['a_src']": "0e3ec88bdc8ddb35",
        "['layers'][0]['w']": "d69f9c5ff84bdc3b",
        "['layers'][1]['a_dst']": "db4d78201ec734b5",
        "['layers'][1]['a_src']": "175dd79b386348bb",
        "['layers'][1]['w']": "1c08119aee899e76"},
}

# logits (2 roots x 5 classes) of SMALL at SEED on the fixed tree of
# _tree(), plain and with the control's float8 operands; same origin
LOGITS = {
    ("sage", "plain"): [
        -0.016092564910650253, -0.01440979540348053, -0.011013353243470192,
        -0.006707068998366594, 0.02269275300204754, -0.3809277415275574,
        -0.5504592657089233, -0.950625479221344, -0.3161925673484802,
        0.7787088751792908],
    ("sage", "fp8"): [
        -0.017578125, -0.0164794921875, -0.0120849609375, -0.0076904296875,
        0.024169921875, -0.33984375, -0.59765625, -1.0859375, -0.341796875,
        0.734375],
    ("gat", "plain"): [
        -0.1067110225558281, 0.046984486281871796, 0.3159228265285492,
        0.1658608466386795, -0.2024252712726593, 0.25052115321159363,
        -0.12757481634616852, -0.6323204040527344, -0.2948291599750519,
        0.2867542803287506],
    ("gat", "fp8"): [
        -0.1015625, 0.056640625, 0.3369140625, 0.172119140625,
        -0.1982421875, 0.203125, -0.203125, -0.728515625, -0.29833984375,
        0.2822265625],
}

# step.mfu's train_flops of each configuration's model; same origin
UNIQUE = ([256, 2531, 24310, 201877], [1, 1, 1, 1],
          [256, 1873, 14960, 90211])
FLOPS = {
    "sage-products": [3417138600.0, 943800.0, 2251258856.0],
    "gat-in2004": [73757068800.0, 1790208.0, 35130370560.0],
}


def _digests(tree) -> dict:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k):
            hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
            for k, v in flat}


def _tree() -> list:
    """Features of a 2-root tree with fanout 2 over 2 hops, 3-d rows."""
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(key_for(7), 3)
    return [jax.random.normal(ks[h], (2 * 2 ** h, 3), jnp.float32)
            for h in range(3)]


@pytest.mark.parametrize("layer", sorted(INIT))
def test_bench_layer_init_pinned(layer):
    params = init_params(SEED, dict(SMALL, layer=layer))
    assert _digests(params) == INIT[layer]


@pytest.mark.parametrize("layer, q", sorted(LOGITS),
                         ids=lambda v: v if isinstance(v, str) else None)
def test_bench_reference_forward_pinned(layer, q):
    import jax
    params = init_params(SEED, dict(SMALL, layer=layer))
    rnd = reference._fp8 if q == "fp8" else (lambda x: x)
    logits = jax.jit(lambda p, fs: reference.forward(p, layer, 2, fs, rnd))(
        params, _tree())
    np.testing.assert_array_equal(
        np.asarray(logits).ravel(), np.asarray(LOGITS[layer, q], np.float32))


@pytest.mark.parametrize("config", sorted(FLOPS))
def test_bench_train_flops_pinned(config):
    mfu = cells.load_metric("step.mfu")
    model = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                       .read_text())["model"]
    assert [mfu.train_flops(u, model) for u in UNIQUE] == FLOPS[config]


def test_bench_load_layer_names_the_missing_file():
    with pytest.raises(FileNotFoundError, match=r"layers/nosuch\.py"):
        cells.load_layer("nosuch")


# A layer type the benchmark has no file for: plain GCN with mean
# normalisation, the equations of the program's models/gnn/layers.py
# gcn_apply written out again.
GCN = '''"""GCN (Kipf and Welling, ICLR 2017) with mean normalisation over the
parent and its f sampled children:

    h' = relu(mean(h, c_1 .. c_f) W + b)

Training FLOPs of layer l: one d_in x d_out matmul per updated vertex
(three times the forward, twice in layer 0) and the sum of its f
children (f * d_in adds; twice, once in layer 0).
"""
import jax
import jax.numpy as jnp

from bench.features import glorot


def init(keys, d_in, d_out, model):
    return {"w": glorot(keys[0], (d_in, d_out)),
            "b": jnp.zeros((d_out,), jnp.float32)}


def apply(p, parent, child, q):
    f = child.shape[1]
    agg = (parent + jnp.sum(child, axis=1)) / (f + 1.0)
    return jax.nn.relu(q(agg) @ q(p["w"]) + p["b"])


def train_flops(l, unique, k, f, d_in, d_out):
    dst = sum(unique[h] for h in range(k - l))
    return ((2.0 if l == 0 else 3.0) * dst * 2 * d_in * d_out
            + (1.0 if l == 0 else 2.0) * dst * f * d_in)
'''


@pytest.fixture()
def jax_config():
    import jax
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_bench_new_layer_type_is_one_file(tmp_path, monkeypatch, jax_config):
    """GCN through the tiny CPU cell, found by ``load_layer`` in a copy of
    the layer directory that holds one more file; the traced run reads
    ``step.mfu`` from the new file's FLOP count."""
    import jax
    from bench import run
    from repro.core import distributed as engine
    from test_bench_run import TINY
    layers = tmp_path / "layers"
    shutil.copytree(cells.LAYERS, layers,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (layers / "gcn.py").write_text(GCN)
    monkeypatch.setattr(cells, "LAYERS", layers)
    assert cells.load_layer("gcn").__file__ == str(layers / "gcn.py")
    v5e = cells.peaks("TPU v5 lite")
    # the traced path without a chip: CPU devices, the v5e's peaks, and
    # no profiler, with step.mfu the one per-layer metric asked for
    monkeypatch.setattr(run, "check_devices",
                        lambda chips, require_tpu: jax.devices()[:chips])
    monkeypatch.setattr(cells, "peaks", lambda kind: v5e)
    monkeypatch.setattr(cells, "per_layer_names",
                        lambda cell, spec=None: ["step.mfu"])
    monkeypatch.setattr(run, "traced_window", lambda training, iters: (
        run.window(training, iters), None))
    cell = copy.deepcopy(TINY)
    cell["config"]["model"]["layer"] = "gcn"
    engine.clear_compile_cache()
    out = run.run_cell(cell, SEED, 0.2, True, say=lambda s: None)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert list(out["metrics"]) == ["step.mfu"]
    assert 0 < out["metrics"]["step.mfu"]["value"] < 100
