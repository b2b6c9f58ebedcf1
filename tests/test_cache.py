"""repro.cache: store/policy/prefetch units, cache-aware planner parity
(vectorized ≡ reference), bit-identical cache-on/off training, the
PlanOverflow → c_max re-bucket path, and the Trainer integration
(hit-rate/refresh accounting, compile-once across refreshes, staleness
guard)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

import repro.core.pregather as pg
from repro.cache import (CacheIndex, CacheStore, DegreePolicy, EpochPrefetcher,
                         LFUPolicy, budget_rows)
from repro.core import distributed as engine
from repro.core import plan_iteration, run_iteration
from repro.core.pregather import (PlanOverflow, _reference_build_gather_plan,
                                  build_gather_plan, workspace_indices,
                                  _reference_workspace_indices)
from repro.models.gnn import GNNConfig, init_gnn
from repro.optim import adam
from repro.train import ShapeBudget, Trainer
from repro.graph.structs import CSRGraph
from repro.graph.partition import shard_features


# ---------------------------------------------------------------------------
# Small deterministic world builders (fixed shapes → one jit trace)
# ---------------------------------------------------------------------------

N_VERT, N_SHARDS, FDIM = 96, 3, 4


def _world(seed: int):
    """Random small graph + even partition + features, fixed sizes."""
    rng = np.random.default_rng(seed)
    n_edges = int(rng.integers(N_VERT, 4 * N_VERT))
    src = rng.integers(0, N_VERT, n_edges)
    dst = rng.integers(0, N_VERT, n_edges)
    graph = CSRGraph.from_edges(N_VERT, src, dst)
    part = (np.arange(N_VERT) % N_SHARDS).astype(np.int32)
    feats = rng.standard_normal((N_VERT, FDIM)).astype(np.float32)
    labels = rng.integers(0, 3, N_VERT).astype(np.int32)
    table, owner, local_idx = shard_features(feats, part, N_SHARDS)
    return dict(graph=graph, part=part, feats=feats, labels=labels,
                table=table, owner=owner, local_idx=local_idx)


def _random_cache(w, rng, k_per_shard: int, c_max: int = 32) -> CacheStore:
    """A store holding an arbitrary valid cached set (not a policy output —
    correctness must hold for any admissible selection)."""
    store = CacheStore(N_SHARDS, FDIM, c_max=c_max)
    ids, rows = [], []
    for s in range(N_SHARDS):
        remote = np.nonzero(w["owner"] != s)[0]
        k = min(k_per_shard, remote.size)
        sel = rng.choice(remote, k, replace=False).astype(np.int64)
        ids.append(sel)
        rows.append(w["feats"][sel])
    store.install(ids, rows)
    return store


def _plan_pair(w, seed: int, store, pregather=True):
    rng = np.random.default_rng(seed)
    roots = [rng.choice(N_VERT, 6, replace=False).astype(np.int64)
             for _ in range(N_SHARDS)]
    kw = dict(num_layers=2, fanout=2, strategy="hopgnn",
              pregather=pregather, sample_seed=seed,
              batch_pad=8, r_max=128)
    args = (w["graph"], w["labels"], w["part"], w["owner"], w["local_idx"],
            w["table"].shape[1], roots)
    return (plan_iteration(*args, **kw),
            plan_iteration(*args, **kw, cache_index=store.index))


# ---------------------------------------------------------------------------
# Store
# ---------------------------------------------------------------------------

def test_store_install_sorts_and_versions():
    w = _world(0)
    store = CacheStore(N_SHARDS, FDIM, c_max=8)
    ids = [np.array([7, 4, 1]), np.array([3]), np.zeros(0, np.int64)]
    # make the ids remote-valid is irrelevant to the store; it stores rows
    rows = [w["feats"][i] for i in ids]
    st0 = store.install(ids, rows)
    assert st0 == {"rows": 4, "bytes": 4 * FDIM * 4, "c_max": 8,
                   "version": 1}
    np.testing.assert_array_equal(store.index.ids[0], [1, 4, 7])
    np.testing.assert_array_equal(store.index.slots[0], [0, 1, 2])
    # table rows land sorted; padding stays zero
    np.testing.assert_array_equal(np.asarray(store.device_table)[0, :3],
                                  w["feats"][[1, 4, 7]])
    assert float(np.abs(np.asarray(store.device_table)[0, 3:]).sum()) == 0.0
    # reinstall bumps the version and replaces the set
    store.install([np.array([2])] + ids[1:], [w["feats"][[2]]] + rows[1:])
    assert store.version == 2 and store.index.version == 2
    assert store.rows_installed() == 2


def test_store_repads_to_next_pow2_bucket():
    store = CacheStore(2, FDIM, c_max=4)
    f = np.zeros((9, FDIM), np.float32)
    store.install([np.arange(3), np.arange(3)], [f[:3], f[:3]])
    assert store.c_max == 4 and store.repads == 0
    store.install([np.arange(9), np.arange(3)], [f, f[:3]])
    assert store.c_max == 16 and store.repads == 1      # pow2 ≥ 9
    assert store.index.c_max == 16


def test_store_rejects_duplicate_ids():
    store = CacheStore(1, FDIM, c_max=4)
    with pytest.raises(ValueError):
        store.install([np.array([5, 5])], [np.zeros((2, FDIM), np.float32)])


def test_hit_split():
    idx = CacheIndex(ids=[np.array([2, 5, 9])], slots=[np.array([0, 1, 2])],
                     c_max=4, version=1)
    hit, slot = idx.hit_split(0, np.array([5, 3, 9, 2, 11]))
    np.testing.assert_array_equal(hit, [True, False, True, True, False])
    np.testing.assert_array_equal(slot[hit], [1, 2, 0])


# ---------------------------------------------------------------------------
# Policies
# ---------------------------------------------------------------------------

def test_budget_rows_math():
    assert budget_rows(0, 128) == 0
    assert budget_rows(1024, 128, 4) == 2
    assert budget_rows(511, 128, 4) == 0


def test_degree_policy_picks_top_degree_remote():
    w = _world(1)
    pol = DegreePolicy(w["graph"], w["owner"])
    deg = w["graph"].degrees()
    for s in range(N_SHARDS):
        sel = pol.select(s, 5)
        assert sel.size == 5
        assert np.all(w["owner"][sel] != s)              # remote only
        remote = np.nonzero(w["owner"] != s)[0]
        worst_kept = deg[sel].min()
        dropped = np.setdiff1d(remote, sel)
        assert deg[dropped].max() <= worst_kept          # top-k by degree


def test_lfu_policy_ranks_by_frequency_and_decays():
    pol = LFUPolicy(1, decay=1.0)
    for _ in range(3):
        pol.observe(0, np.array([10, 11]))
    pol.observe(0, np.array([12]))
    np.testing.assert_array_equal(pol.select(0, 2), [10, 11])
    # exact forecast overrides history entirely
    sel = pol.select(0, 2, hot_ids=np.array([30, 12, 31]),
                     hot_counts=np.array([5, 1, 4]))
    np.testing.assert_array_equal(sel, [30, 31])
    # decay: old counts fade
    pol2 = LFUPolicy(1, decay=0.1)
    pol2.observe(0, np.array([1]), np.array([4.0]))
    pol2.select(0, 1)                                    # applies decay once
    pol2.observe(0, np.array([2]), np.array([1.0]))
    np.testing.assert_array_equal(pol2.select(0, 1), [2])


# ---------------------------------------------------------------------------
# Cache-aware planner: vectorized ≡ reference (both dedup paths)
# ---------------------------------------------------------------------------

def _check_plan_parity(seed, k_cache, dense, monkeypatch=None):
    if monkeypatch is not None:
        monkeypatch.setattr(pg, "_DENSE_DEDUP_MAX_CELLS",
                            (1 << 28) if dense else 0)
    w = _world(seed)
    rng = np.random.default_rng(seed + 77)
    store = _random_cache(w, rng, k_cache)
    needed = [rng.integers(0, N_VERT, int(rng.integers(0, 300)))
              for _ in range(N_SHARDS)]
    a = build_gather_plan(needed, w["owner"], w["local_idx"], N_SHARDS,
                          w["table"].shape[1], cache=store.index)
    b = _reference_build_gather_plan(needed, w["owner"], w["local_idx"],
                                     N_SHARDS, w["table"].shape[1],
                                     cache=store.index)
    np.testing.assert_array_equal(a.req, b.req)
    np.testing.assert_array_equal(a.req_count, b.req_count)
    assert a.r_max == b.r_max and a.c_max == b.c_max == store.c_max
    np.testing.assert_array_equal(a.cache_hits, b.cache_hits)
    np.testing.assert_array_equal(a.slot_map.starts, b.slot_map.starts)
    np.testing.assert_array_equal(a.slot_map.ids, b.slot_map.ids)
    np.testing.assert_array_equal(a.slot_map.slots, b.slot_map.slots)
    # hop translation parity through the cached slots
    for s in range(N_SHARDS):
        if needed[s].size == 0:
            continue
        hops = [needed[s][rng.integers(0, needed[s].size, 64)]]
        wa = workspace_indices(hops, s, w["owner"], w["local_idx"], a)
        wb = _reference_workspace_indices(hops, s, w["owner"],
                                          w["local_idx"], b)
        np.testing.assert_array_equal(wa[0], wb[0])
    # hit slots live in the cached region, miss slots above it
    local_rows = w["table"].shape[1]
    for s in range(N_SHARDS):
        ids = a.slot_map.shard_ids(s)
        slots = a.slot_map.shard_slots(s)
        hit, _ = store.index.hit_split(s, ids)
        assert np.all(slots[hit] < local_rows + a.c_max)
        assert np.all(slots[hit] >= local_rows)
        assert np.all(slots[~hit] >= local_rows + a.c_max)


@given(st.integers(0, 10_000), st.integers(0, 30), st.booleans())
@settings(max_examples=25, deadline=None)
def test_cache_plan_parity_property(seed, k_cache, dense):
    """Vectorized cache-aware planner ≡ per-vertex reference on random
    graphs, cached sets, and both dedup paths."""
    old = pg._DENSE_DEDUP_MAX_CELLS
    pg._DENSE_DEDUP_MAX_CELLS = (1 << 28) if dense else 0
    try:
        _check_plan_parity(seed, k_cache, dense)
    finally:
        pg._DENSE_DEDUP_MAX_CELLS = old


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("dense", [True, False])
def test_cache_plan_parity_seeded(seed, dense, monkeypatch):
    _check_plan_parity(seed, k_cache=(seed * 5) % 31, dense=dense,
                       monkeypatch=monkeypatch)


# ---------------------------------------------------------------------------
# Bit-identical iteration: cache-on ≡ cache-off (the tentpole invariant)
# ---------------------------------------------------------------------------

def _grad_dmax(g0, g1):
    return max(float(jnp.abs(a - b).max())
               for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)))


@given(st.integers(0, 10_000), st.integers(1, 24))
@settings(max_examples=8, deadline=None)
def test_cached_run_iteration_bit_identical_property(seed, k_cache):
    """Cache-enabled run_iteration ≡ cache-disabled, bit for bit, for
    random graphs/selections/budgets. Shapes are pinned (batch_pad/r_max/
    c_max fixed) so the whole property run shares one compiled program."""
    w = _world(seed)
    rng = np.random.default_rng(seed + 1)
    store = _random_cache(w, rng, k_cache)       # c_max pinned to 32
    cfg = GNNConfig(model="sage", num_layers=2, hidden_dim=8,
                    feature_dim=FDIM, num_classes=3, fanout=2)
    params = init_gnn(jax.random.PRNGKey(seed % 7), cfg)
    p0, p1 = _plan_pair(w, seed, store)
    assert p1.cache_hit_rows + p1.remote_rows_exact == p0.remote_rows_exact
    g0, l0 = run_iteration(params, w["table"], p0, cfg)
    g1, l1 = run_iteration(params, w["table"], p1, cfg,
                           cache=store.device_table)
    assert float(l0) == float(l1)
    assert _grad_dmax(g0, g1) == 0.0


@pytest.mark.parametrize("pregather", [True, False])
def test_cached_run_iteration_bit_identical_seeded(pregather):
    """Always-on variant of the property test, covering per-step mode
    (where the cache also dedups across steps) and the folded/unfolded
    feature-return paths."""
    w = _world(3)
    rng = np.random.default_rng(3)
    store = _random_cache(w, rng, 16)
    cfg = GNNConfig(model="sage", num_layers=2, hidden_dim=8,
                    feature_dim=FDIM, num_classes=3, fanout=2)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    p0, p1 = _plan_pair(w, 3, store, pregather=pregather)
    g0, l0 = run_iteration(params, w["table"], p0, cfg)
    g1, l1 = run_iteration(params, w["table"], p1, cfg,
                           cache=store.device_table)
    assert float(l0) == float(l1) and _grad_dmax(g0, g1) == 0.0
    if not pregather:
        gf, lf = run_iteration(params, w["table"], p1, cfg,
                               cache=store.device_table, fold_returns=True)
        gu, lu = run_iteration(params, w["table"], p1, cfg,
                               cache=store.device_table, fold_returns=False)
        assert float(lf) == float(lu) == float(l0)
        assert _grad_dmax(gf, gu) == 0.0


def test_run_iteration_guards_cache_table():
    w = _world(5)
    rng = np.random.default_rng(5)
    store = _random_cache(w, rng, 8)
    cfg = GNNConfig(model="sage", num_layers=2, hidden_dim=8,
                    feature_dim=FDIM, num_classes=3, fanout=2)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    _, p1 = _plan_pair(w, 5, store)
    with pytest.raises(ValueError, match="no cache table"):
        run_iteration(params, w["table"], p1, cfg)           # cache missing
    with pytest.raises(ValueError, match="c_max"):
        run_iteration(params, w["table"], p1, cfg,
                      cache=jnp.zeros((N_SHARDS, 8, FDIM)))  # wrong height


# ---------------------------------------------------------------------------
# PlanOverflow("c_max") → ShapeBudget re-bucket
# ---------------------------------------------------------------------------

def test_c_max_overflow_and_rebucket():
    w = _world(7)
    rng = np.random.default_rng(7)
    store = _random_cache(w, rng, 16, c_max=16)
    roots = [rng.choice(N_VERT, 6, replace=False).astype(np.int64)
             for _ in range(N_SHARDS)]
    kw = dict(graph=w["graph"], labels=w["labels"], part=w["part"],
              owner=w["owner"], local_idx=w["local_idx"],
              local_rows=w["table"].shape[1], roots_per_model=roots,
              num_layers=2, fanout=2, strategy="hopgnn", sample_seed=7)
    # direct overflow: a c_max budget below the index height is structured
    with pytest.raises(PlanOverflow) as ei:
        plan_iteration(**kw, cache_index=store.index, c_max=8)
    assert (ei.value.field, ei.value.needed, ei.value.limit) == \
        ("c_max", 16, 8)

    # ShapeBudget: learns c_max from the first plan, then re-buckets
    # explicitly when the store re-pads (cache-size drift)
    budget = ShapeBudget()
    p1 = budget.plan(**kw, cache_index=store.index)
    assert budget.c_max == 16 and p1.c_max == 16 and budget.rebuckets == 0
    big = np.nonzero(w["owner"] != 0)[0][:20].astype(np.int64)
    store.install([big] + [store.index.ids[s] for s in (1, 2)],
                  [w["feats"][big]] + [w["feats"][store.index.ids[s]]
                                       for s in (1, 2)])
    assert store.c_max == 32                     # re-padded past the budget
    p2 = budget.plan(**kw, cache_index=store.index)
    assert budget.rebuckets == 1 and budget.c_max == 32 and p2.c_max == 32
    # shapes stable afterwards: same bucket, no further growth
    p3 = budget.plan(**kw, cache_index=store.index)
    assert budget.rebuckets == 1 and p3.c_max == 32


# ---------------------------------------------------------------------------
# Deterministic epoch prefetch
# ---------------------------------------------------------------------------

def test_prefetcher_predicts_plan_requests_exactly(partitioned):
    """The replayed hot sets must equal the remote request sets the
    Trainer's plans actually make (same roots, same stateless sampler)."""
    d = partitioned
    cfg = GNNConfig(model="sage", num_layers=2, hidden_dim=16,
                    feature_dim=d["ds"].feature_dim,
                    num_classes=d["ds"].num_classes, fanout=4)
    tr = Trainer(graph=d["ds"].graph, labels=d["ds"].labels, part=d["part"],
                 owner=d["owner"], local_idx=d["local_idx"],
                 table=d["table"], cfg=cfg, optimizer=adam(5e-3),
                 merging=False, train_vertices=d["ds"].train_vertices(),
                 cache_policy="lfu",
                 cache_budget_bytes=64 * d["ds"].feature_dim * 4)
    tr._prefetch_batch = 8
    pf = tr._cache_prefetcher
    for it in range(2):
        pred = pf.iteration_requests(1, it)
        plan = tr.build_plan(1, it, 8)
        for s in range(d["parts"]):
            np.testing.assert_array_equal(np.sort(pred[s]),
                                          plan.remote_ids[s])


# ---------------------------------------------------------------------------
# Trainer integration
# ---------------------------------------------------------------------------

def _mk_trainer(d, **kw):
    cfg = GNNConfig(model="sage", num_layers=2, hidden_dim=16,
                    feature_dim=d["ds"].feature_dim,
                    num_classes=d["ds"].num_classes, fanout=4)
    kw.setdefault("optimizer", adam(5e-3))
    kw.setdefault("merging", False)
    kw.setdefault("train_vertices", d["ds"].train_vertices())
    return Trainer(graph=d["ds"].graph, labels=d["ds"].labels,
                   part=d["part"], owner=d["owner"],
                   local_idx=d["local_idx"], table=d["table"], cfg=cfg, **kw)


def test_trainer_cache_training_is_bit_identical(partitioned):
    """Same seeds, cache on vs off: identical per-epoch losses and final
    parameters, while the cache actually serves hits and refreshes never
    retrace (trace_log clean after epoch 0)."""
    d = partitioned
    engine.clear_compile_cache()
    t0 = _mk_trainer(d)
    s0 = t0.fit(epochs=3, iters_per_epoch=3, batch_per_model=8)

    engine.clear_compile_cache()
    t1 = _mk_trainer(d, cache_policy="lfu",
                     cache_budget_bytes=2048 * d["ds"].feature_dim * 4)
    s1 = t1.fit(epochs=3, iters_per_epoch=3, batch_per_model=8)

    assert [st.loss for st in s1] == [st.loss for st in s0]
    for a, b in zip(jax.tree.leaves(t0.params), jax.tree.leaves(t1.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # covering budget + exact forecast: steady epochs are all-hit
    assert s1[1].cache_hit_rate == 1.0 and s1[2].cache_hit_rate == 1.0
    assert s1[1].remote_rows == 0
    assert s1[1].cache_bytes_saved > 0
    # epoch-boundary refreshes must not retrace (the compile-once contract)
    assert s1[1].traces == 0 and s1[2].traces == 0
    # misses + hits must equal the cache-off remote rows
    for off, on in zip(s0, s1):
        assert on.cache_hit_rows + on.remote_rows == off.remote_rows


def test_trainer_degree_cache_hits_without_prefetch_thread(partitioned):
    d = partitioned
    engine.clear_compile_cache()
    tr = _mk_trainer(d, cache_policy="degree",
                     cache_budget_bytes=256 * d["ds"].feature_dim * 4)
    stats = tr.fit(epochs=2, iters_per_epoch=3, batch_per_model=8)
    assert tr.cache_store.installs == 1          # static: one install, ever
    assert all(st.cache_hit_rows > 0 for st in stats)
    assert stats[1].traces == 0


def test_trainer_rejects_stale_cache_plan(partitioned):
    d = partitioned
    tr = _mk_trainer(d, cache_policy="degree",
                     cache_budget_bytes=64 * d["ds"].feature_dim * 4)
    tr._cache_select_install()
    plan = tr.build_plan(0, 0, 8)
    tr._cache_select_install()                   # version bump → plan stale
    with pytest.raises(RuntimeError, match="stale cache plan"):
        tr.train_step(plan)


def test_trainer_zero_budget_disables_cache(partitioned):
    d = partitioned
    tr = _mk_trainer(d, cache_policy="lfu", cache_budget_bytes=0)
    assert not tr.cache_enabled
    stats = tr.fit(epochs=1, iters_per_epoch=2, batch_per_model=8)
    assert stats[0].cache_hit_rows == 0


# ---------------------------------------------------------------------------
# Merge-pattern-aware prediction (the ROADMAP "cache vs merging gap")
# ---------------------------------------------------------------------------

def test_prefetcher_fold_steps_predicts_merged_requests(partitioned):
    """Under a §5.3 merge the requesting shard moves for the merged roots:
    the unfolded replay must mispredict some shard's request set, and the
    fold_steps hook (folding exactly like build_plan) must restore exact
    prediction."""
    from repro.core.merging import MergingController
    from repro.core.micrograph import hopgnn_assignment
    d = partitioned
    tr = _mk_trainer(d, cache_policy="lfu",
                     cache_budget_bytes=64 * d["ds"].feature_dim * 4,
                     merging=True, root_seed=11)
    tr._prefetch_batch = 8
    steps = d["parts"] - 1
    tr.controller = MergingController(
        base=hopgnn_assignment(tr._roots_for(0, 0, 8), d["part"]))
    tr.controller.restore(num_steps=steps, frozen=True)

    pf = tr._cache_prefetcher                 # fold_steps wired by Trainer
    unfolded = EpochPrefetcher(
        graph=d["ds"].graph, part=d["part"], owner=d["owner"],
        num_shards=d["parts"], num_layers=2, fanout=4,
        roots_for=tr._prefetch_roots_for,
        sample_seed_for=lambda e, i: tr.sample_seed_base + e * 10_000 + i,
        strategy="hopgnn")

    mismatch = False
    for it in range(2):
        pred = pf.iteration_requests(1, it)
        pred_raw = unfolded.iteration_requests(1, it)
        plan = tr.build_plan(1, it, 8)        # folds via controller pattern
        assert plan.num_steps == steps
        for s in range(d["parts"]):
            np.testing.assert_array_equal(np.sort(pred[s]),
                                          plan.remote_ids[s])
            if not np.array_equal(np.sort(pred_raw[s]), plan.remote_ids[s]):
                mismatch = True
    assert mismatch        # the gap is real: unfolded prediction is wrong


def test_merged_frozen_pattern_recovers_full_hit_rate(partitioned):
    """Regression for the prediction gap: with an active (frozen) merge
    and a covering LFU budget, prefetch-driven steady epochs must be
    all-hit — exactly like the unmerged benchmark configuration."""
    d = partitioned
    engine.clear_compile_cache()
    from repro.core.merging import MergingController
    from repro.core.micrograph import hopgnn_assignment
    tr = _mk_trainer(d, cache_policy="lfu", merging=True, root_seed=11,
                     cache_budget_bytes=4096 * d["ds"].feature_dim * 4)
    tr.controller = MergingController(
        base=hopgnn_assignment(tr._roots_for(0, 0, 8), d["part"]))
    tr.controller.restore(num_steps=d["parts"] - 1, frozen=True)
    stats = tr.fit(epochs=3, iters_per_epoch=3, batch_per_model=8)
    assert all(st.num_steps == d["parts"] - 1 for st in stats)
    # epoch 0 runs cold (no forecast yet); steady epochs are all-hit
    assert stats[1].cache_hit_rate == 1.0 and stats[2].cache_hit_rate == 1.0
    assert stats[1].remote_rows == 0 and stats[2].remote_rows == 0
    assert stats[1].traces == 0 and stats[2].traces == 0
