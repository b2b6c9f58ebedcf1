"""HopGNN core: planner, pre-gathering, merging, comm model, and the
gradient-parity (accuracy fidelity) invariant."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import plan_iteration, run_iteration, MergingController
from repro.core.comm_model import (ModelSpec, alpha_ratio, hopgnn_bytes,
                                   lo_bytes, model_centric_bytes,
                                   naive_fc_bytes, p3_bytes)
from repro.core.merging import merge_min_step
from repro.core.micrograph import hopgnn_assignment, model_centric_assignment
from repro.core.pregather import build_gather_plan
from repro.graph.sampler import micrograph_split, sample_tree_block
from repro.models.gnn import GNNConfig, init_gnn


def _roots(partitioned, per_model=12, seed=0):
    rng = np.random.default_rng(seed)
    tv = partitioned["ds"].train_vertices()
    return [rng.choice(tv, per_model, replace=False)
            for _ in range(partitioned["parts"])]


def _plan(partitioned, strategy, seed=7, **kw):
    d = partitioned
    return plan_iteration(
        d["ds"].graph, d["ds"].labels, d["part"], d["owner"],
        d["local_idx"], d["table"].shape[1], _roots(d),
        num_layers=2, fanout=4, strategy=strategy, sample_seed=seed, **kw)


# ---------------------------------------------------------------------------
# Assignment / redistribution
# ---------------------------------------------------------------------------

def test_hopgnn_assignment_preserves_batch_composition(partitioned):
    """§5.1: model d trains exactly its original mini-batch, only placement
    changes — the accuracy-fidelity precondition."""
    roots = _roots(partitioned)
    amat = hopgnn_assignment(roots, partitioned["part"])
    per_model = {d: [] for d in range(len(roots))}
    for (s, t), gs in amat.groups.items():
        for d, r in gs:
            per_model[d].append(r)
            # rotation schedule: model d is on server (d + t) % N at step t
            assert s == (d + t) % amat.num_shards
    for d, orig in enumerate(roots):
        got = np.sort(np.concatenate(per_model[d]))
        np.testing.assert_array_equal(got, np.sort(orig))


def test_root_redistribution_by_home(partitioned):
    roots = _roots(partitioned)
    amat = hopgnn_assignment(roots, partitioned["part"])
    for (s, t), gs in amat.groups.items():
        for d, r in gs:
            assert np.all(partitioned["part"][r] == s)  # homed correctly


# ---------------------------------------------------------------------------
# Pre-gathering (§5.2)
# ---------------------------------------------------------------------------

@given(st.integers(2, 6), st.integers(1, 40), st.integers(0, 500))
@settings(max_examples=20, deadline=None)
def test_gather_plan_is_deduped_cover(n_shards, n_ids, seed):
    rng = np.random.default_rng(seed)
    n_vertices = 100
    owner = rng.integers(0, n_shards, n_vertices).astype(np.int32)
    local_idx = np.zeros(n_vertices, np.int32)
    for s in range(n_shards):
        ids = np.nonzero(owner == s)[0]
        local_idx[ids] = np.arange(ids.size)
    needed = [rng.integers(0, n_vertices, n_ids) for _ in range(n_shards)]
    plan = build_gather_plan(needed, owner, local_idx, n_shards,
                             local_rows=int(np.bincount(owner).max()))
    for s in range(n_shards):
        # every remote id needed has a slot; no remote id fetched twice
        remote = np.unique(needed[s][owner[needed[s]] != s])
        np.testing.assert_array_equal(plan.slot_map.shard_ids(s), remote)
        assert np.unique(plan.slot_map.shard_slots(s)).size == remote.size
        assert plan.req_count[s].sum() == remote.size      # dedup exact
        assert plan.req_count[s, s] == 0                   # never self-fetch


def test_pregather_saves_vs_per_step(partitioned):
    """§5.2: deduped cross-step fetch count ≤ per-step fetch count."""
    p_pre = _plan(partitioned, "hopgnn", pregather=True)
    p_per = _plan(partitioned, "hopgnn", pregather=False)
    assert p_pre.remote_rows_exact <= p_per.remote_rows_exact
    assert p_pre.remote_rows_exact <= p_pre.remote_rows_nodedup


def test_hopgnn_beats_model_centric_on_miss_rate(partitioned):
    """Fig. 14: micrograph training cuts the remote-feature miss rate."""
    ph = _plan(partitioned, "hopgnn")
    pm = _plan(partitioned, "model_centric")
    assert ph.miss_rate() < pm.miss_rate()


def test_lo_zero_remote(partitioned):
    pl = _plan(partitioned, "lo")
    assert pl.remote_rows_exact == 0
    assert pl.num_steps == 1


# ---------------------------------------------------------------------------
# Gradient parity (Table 3 as a theorem, not a statistic)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["gcn", "sage", "gat", "film"])
def test_gradient_parity_hopgnn_vs_model_centric(partitioned, model):
    d = partitioned
    cfg = GNNConfig(model=model, num_layers=2, hidden_dim=32,
                    feature_dim=d["ds"].feature_dim,
                    num_classes=d["ds"].num_classes, fanout=4)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    gm, lm = run_iteration(params, d["table"],
                           _plan(d, "model_centric"), cfg)
    gh, lh = run_iteration(params, d["table"], _plan(d, "hopgnn"), cfg)
    assert abs(float(lm) - float(lh)) < 1e-4
    for a, b in zip(jax.tree.leaves(gm), jax.tree.leaves(gh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-5)


def test_per_step_batched_exchange_gradient_parity(partitioned):
    """Per-step mode (batched index exchange hoisted ahead of the scan)
    must train bit-identically to pregather mode: same tree blocks, same
    feature rows, only the fetch schedule differs."""
    d = partitioned
    cfg = GNNConfig(model="sage", num_layers=2, hidden_dim=32,
                    feature_dim=d["ds"].feature_dim,
                    num_classes=d["ds"].num_classes, fanout=4)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    gp, lp = run_iteration(params, d["table"],
                           _plan(d, "hopgnn", pregather=True), cfg)
    gs, ls = run_iteration(params, d["table"],
                           _plan(d, "hopgnn", pregather=False), cfg)
    assert float(lp) == float(ls)                  # bit-identical loss
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lo_gradient_differs(partitioned):
    """The LO baseline *changes* batch composition — its gradient must NOT
    match (that's the bias the paper warns about in §7.9)."""
    d = partitioned
    cfg = GNNConfig(model="gcn", num_layers=2, hidden_dim=32,
                    feature_dim=d["ds"].feature_dim,
                    num_classes=d["ds"].num_classes, fanout=4)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    gm, _ = run_iteration(params, d["table"], _plan(d, "model_centric"), cfg)
    gl, _ = run_iteration(params, d["table"], _plan(d, "lo"), cfg)
    diffs = [float(jnp.abs(a - b).max())
             for a, b in zip(jax.tree.leaves(gm), jax.tree.leaves(gl))]
    assert max(diffs) > 1e-6


# ---------------------------------------------------------------------------
# Merging (§5.3)
# ---------------------------------------------------------------------------

def test_merge_min_step_conserves_roots(partitioned):
    roots = _roots(partitioned)
    amat = hopgnn_assignment(roots, partitioned["part"])
    merged = merge_min_step(amat)
    assert merged.num_steps == amat.num_steps - 1
    # per-model totals conserved (Fig. 10 invariant)
    np.testing.assert_array_equal(amat.model_step_counts().sum(0),
                                  merged.model_step_counts().sum(0))


def test_merge_min_step_dedupes_duplicate_target_slots():
    """A model with several groups at one (server, step) slot — the normal
    state after a previous merge round — must count that slot once when the
    folded roots are redistributed, or array_split over-weights it."""
    from repro.core.micrograph import AssignmentMatrix
    groups = {
        (0, 0): [(0, np.arange(0, 3)), (0, np.arange(3, 6))],  # dup slot
        (1, 1): [(0, np.arange(6, 9))],
        (0, 2): [(0, np.arange(9, 13))],                       # folded step
    }
    amat = AssignmentMatrix(num_shards=2, num_steps=3, groups=groups)
    merged = merge_min_step(amat, ts_min=2)
    assert merged.num_steps == 2
    # exact conservation of the model's roots
    got = np.sort(np.concatenate(
        [r for gs in merged.groups.values() for _, r in gs]))
    np.testing.assert_array_equal(got, np.arange(13))
    # even redistribution over the two *distinct* slots: 2 roots each
    # (the duplicated (0,0) slot previously got 3 of the 4)
    assert merged.roots_at(0, 0).size == 6 + 2
    assert merged.roots_at(1, 1).size == 3 + 2


def test_merging_controller_freezes_on_regression():
    roots = [np.arange(8) * 4 + i for i in range(4)]
    part = np.arange(64) % 4
    base = hopgnn_assignment(roots, part.astype(np.int32))
    ctl = MergingController(base=base)
    ctl.record_epoch_time(10.0)      # epoch 0 baseline
    s1 = ctl.assignment_for_epoch().num_steps
    ctl.record_epoch_time(8.0)       # improved -> merge again
    s2 = ctl.assignment_for_epoch().num_steps
    ctl.record_epoch_time(9.0)       # regressed -> revert to s1 + freeze
    assert ctl.frozen
    assert ctl.assignment_for_epoch().num_steps == s1  # pre-regression wins
    assert s2 == s1 - 1


def test_micrograph_locality_stats_per_root_home():
    """Locality must be scored against each root's own home server, not the
    first root's: a 2-root block whose subtrees are each fully local to
    their own root is 100 % local."""
    from repro.core.micrograph import micrograph_locality_stats
    part = np.array([0, 0, 1, 1])
    hops = [np.array([0, 2]),              # roots homed at 0 and 1
            np.array([0, 1, 2, 3])]        # each root's children all local
    local, remote = micrograph_locality_stats([hops], part)
    assert local == 1.0 and remote == 0.0
    # mixed case: root 1's children live on server 0 -> half remote
    hops2 = [np.array([0, 2]), np.array([0, 1, 0, 1])]
    local2, remote2 = micrograph_locality_stats([hops2], part)
    assert local2 == 0.5 and remote2 == 0.5


# ---------------------------------------------------------------------------
# Comm model (Fig. 5 / Fig. 7)
# ---------------------------------------------------------------------------

def _blocks_for(partitioned, seed=0):
    d = partitioned
    rng = np.random.default_rng(seed)
    roots = rng.integers(0, d["ds"].num_vertices, 16)
    blk = sample_tree_block(d["ds"].graph, roots, 3, 4, seed=5)
    micros = micrograph_split(blk)
    shard_of = [int(rng.integers(0, d["parts"])) for _ in micros]
    return micros, shard_of


def test_comm_model_strategies_ordered(partitioned):
    """Fig. 7/11 ordering on a locality partition: lo < hopgnn(SPMD) <
    model-centric, and naive pays intermediate-data overhead."""
    d = partitioned
    micros, shard_of = _blocks_for(d)
    spec = ModelSpec(feature_dim=128, hidden_dim=128, num_layers=3,
                     param_bytes=200_000)
    mc = model_centric_bytes(micros, d["owner"], shard_of, spec, d["parts"])
    nv = naive_fc_bytes(micros, d["owner"], spec, d["parts"])
    hp = hopgnn_bytes(int(mc["remote_rows"] * 0.4), d["parts"], spec,
                      d["parts"], replicated_params=True)
    lo = lo_bytes(spec, d["parts"])
    assert lo["total"] <= hp["total"] <= mc["total"]
    assert nv["intermediate_bytes"] > 0 and nv["migrations"] > 0
    p3 = p3_bytes(micros, d["owner"], shard_of, spec, d["parts"])
    assert p3["feature_bytes"] == 0          # P³ never ships raw features


def test_alpha_ratio_regime():
    """Fig. 5: α ≫ 1 for realistic GNN shapes (the motivation)."""
    # 3-layer subgraph, fanout 10, batch 1024 roots, dim 128 features
    remote_rows = 1024 * (10 + 100 + 1000) // 2
    a = alpha_ratio(remote_rows, 128, param_bytes=200_000)
    assert a > 13.4          # the paper's observed minimum


def test_hopgnn_paper_faithful_migration_cost():
    spec = ModelSpec(feature_dim=600, hidden_dim=16, num_layers=3,
                     param_bytes=50_000)
    faithful = hopgnn_bytes(1000, 4, spec, 4, replicated_params=False)
    spmd = hopgnn_bytes(1000, 4, spec, 4, replicated_params=True)
    assert faithful["model_bytes"] > 0 and spmd["model_bytes"] == 0
    assert faithful["total"] > spmd["total"]


# ---------------------------------------------------------------------------
# Executable P³ baseline (core/p3.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["gcn", "sage", "gat"])
def test_p3_gradient_parity(partitioned, model):
    """P³'s dim-sliced input layer + psum is placement-only: gradients must
    equal model-centric training exactly (like HopGNN's parity)."""
    import jax.numpy as jnp
    from repro.core.p3 import plan_p3, run_p3_iteration
    d = partitioned
    cfg = GNNConfig(model=model, num_layers=2, hidden_dim=32,
                    feature_dim=d["ds"].feature_dim,
                    num_classes=d["ds"].num_classes, fanout=4)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    roots = _roots(d)
    gm, lm = run_iteration(params, d["table"],
                           _plan(d, "model_centric"), cfg)
    p3p = plan_p3(d["ds"].graph, d["ds"].labels, roots, num_layers=2,
                  fanout=4, hidden_dim=32, sample_seed=7)
    g3, l3 = run_p3_iteration(params, jnp.asarray(d["ds"].features),
                              p3p, cfg)
    assert abs(float(lm) - float(l3)) < 1e-4
    for a, b in zip(jax.tree.leaves(gm), jax.tree.leaves(g3)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-5)


def test_p3_rejects_norm_fronted_models(partitioned):
    from repro.core.p3 import P3Unsupported, plan_p3, run_p3_iteration
    import jax.numpy as jnp
    d = partitioned
    cfg = GNNConfig(model="deepgcn", num_layers=2, hidden_dim=32,
                    feature_dim=d["ds"].feature_dim,
                    num_classes=d["ds"].num_classes, fanout=4)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    p3p = plan_p3(d["ds"].graph, d["ds"].labels, _roots(d), num_layers=2,
                  fanout=4, hidden_dim=32)
    with pytest.raises(P3Unsupported):
        run_p3_iteration(params, jnp.asarray(d["ds"].features), p3p, cfg)


def test_p3_never_moves_raw_features(partitioned):
    """P³'s activation bytes scale with hidden dim, never feature dim —
    the structural property behind its hidden-dim sensitivity (§7.2)."""
    from repro.core.p3 import plan_p3
    d = partitioned
    roots = _roots(d)
    small = plan_p3(d["ds"].graph, d["ds"].labels, roots, 2, 4,
                    hidden_dim=16, sample_seed=1)
    big = plan_p3(d["ds"].graph, d["ds"].labels, roots, 2, 4,
                  hidden_dim=256, sample_seed=1)
    assert big.activation_bytes() == 16 * small.activation_bytes()
