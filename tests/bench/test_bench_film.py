"""The GNN-FiLM cell's files: the reference equations of
``bench/layers/film.py`` equal the program's layer, the cell's limits
catch the float8 control, half a batch and a missing exchange, and the
configuration runs sage-products' graph."""
import copy
import functools
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cell as cells  # noqa: E402
from bench import reference  # noqa: E402

CELL = "film-products.train"
SEED = 2 ** 33 + 5


def test_bench_film_apply_is_the_programs_layer():
    """With ``q`` the identity, the benchmark's equations and the program's
    ``film_apply`` give the same rows from the same weights, both in
    float32 at ``"highest"``: the gap is rounding in another order of sums
    (1e-5 relative, 1e-6 absolute near zero)."""
    import jax
    import jax.numpy as jnp
    from repro.models.gnn.layers import film_apply
    layer = cells.load_layer("film")
    keys = jax.random.split(jax.random.key(3), 6)
    p = layer.init(tuple(keys[:3]), 8, 16, {})
    # random shifts and LayerNorm parameters, so every term shows
    for k, name in zip(keys[3:], ("film_nbr_b", "film_self_b", "ln_b")):
        p[name] = jax.random.normal(k, p[name].shape, jnp.float32)
    p["ln_g"] = 1.0 + 0.5 * p["ln_b"]
    parent = jax.random.normal(jax.random.key(4), (24, 8), jnp.float32)
    child = jax.random.normal(jax.random.key(5), (24, 3, 8), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = layer.apply(p, parent, child, lambda x: x)
        want = film_apply(p, parent, child)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@functools.lru_cache(maxsize=None)
def _tiny_cell():
    """The cell's model on the tiny graph of ``test_bench_run``: its
    configuration, the reference, the first three batches, the initial
    weights and the float32 reference run over them."""
    import jax
    from bench.features import init_params
    from bench.run import build_data
    from bench.traffic import Traffic
    from test_bench_run import TINY
    tiny = copy.deepcopy(TINY)
    tiny["config"]["model"] = dict(cells.load_cell(CELL)["config"]["model"])
    cfg, tp = tiny["config"], tiny["traffic"]
    data = build_data(cfg, lambda s: None)
    traffic = Traffic(tp, data.graph.train_vertices(), 4, SEED)
    p0 = jax.device_get(init_params(SEED, cfg["model"]))
    ref = reference.Reference(cfg, data.graph, data.owner, data.local_idx,
                              data.rows)
    batches = [(traffic.roots(g), traffic.sample_seed(g)) for g in range(3)]
    exact = reference.trajectory(ref, cfg, jax.device_put(p0), batches)
    return cfg, ref, batches, p0, exact


def _check(**fault) -> dict:
    """The numbers the check compares for the reference run with
    ``fault`` (``trajectory``'s keywords) against the float32 reference,
    on the tiny graph."""
    import jax
    cfg, ref, batches, p0, exact = _tiny_cell()
    run = reference.trajectory(ref, cfg, jax.device_put(p0), batches,
                               **fault)
    return reference.compare(run, exact, p0)


def test_bench_film_check_fails_half_a_batch():
    """The reference trained on half of each batch fails the cell's
    limits on a tiny graph, with the cell's model."""
    limits = cells.load_cell(CELL)["workload"]["limits"]
    nums = _check(keep=0.5)
    assert any(nums[k] > limits[k] for k in limits), nums


@pytest.mark.parametrize("fault", [{"control": True},
                                   {"drop_remote": True}],
                         ids=["float8_control", "no_exchange"])
def test_bench_film_check_fails_the_control_and_a_missing_exchange(fault):
    """The float8 control (every matmul operand in e4m3) and a step that
    reads every remote feature row as zero fail the cell's limits on a
    tiny graph, with the cell's model, on ``grad``: at 10 layers the
    first three steps' ``loss`` and ``change`` move by as much from any
    rounding, once AdamW's first step has flipped the signs of the
    smallest gradient entries (PERF.md, section 2)."""
    limits = cells.load_cell(CELL)["workload"]["limits"]
    nums = _check(**fault)
    assert nums["grad"] > limits["grad"], nums


def test_bench_film_runs_the_sage_products_graph():
    """Same generator parameters and data seed, so the graph the
    benchmark caches for sage-products serves this cell too; same
    optimizer. The FiLM layers' matmuls take float32 operands where
    sage's take bfloat16 ones."""
    film = cells.load_cell(CELL)["config"]
    sage = cells.load_cell("sage-products.train")["config"]
    for key in ("graph", "published", "produced", "optimizer", "workers"):
        assert film[key] == sage[key], key
    assert film["precision"]["matmul_operands"] == "float32"
    assert sage["precision"]["matmul_operands"] == "bfloat16"
