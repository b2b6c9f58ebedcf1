"""Graph substrate: CSR invariants, partitioners, sampler (incl. property
tests with hypothesis)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.graph import (CSRGraph, hash_partition, ldg_partition,
                         make_dataset, range_partition, sample_tree_block)
from repro.graph.partition import (edge_cut, local_index_map, partition_sizes,
                                   shard_features)
from repro.graph.sampler import group_roots_by_home, micrograph_split


# ---------------------------------------------------------------------------
# CSR
# ---------------------------------------------------------------------------

@given(st.integers(4, 64), st.integers(1, 200), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_csr_from_edges_invariants(n, m, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    g = CSRGraph.from_edges(n, src, dst, symmetrize=True)
    assert g.indptr.shape == (n + 1,)
    assert g.indptr[0] == 0 and g.indptr[-1] == g.num_edges
    assert np.all(np.diff(g.indptr) >= 0)
    assert np.all(g.indices >= 0) and np.all(g.indices < n)
    # symmetry: (u,v) present => (v,u) present
    for u in range(n):
        for v in g.neighbors(u):
            assert u in g.neighbors(int(v)), (u, v)
    # no self loops
    for u in range(n):
        assert u not in g.neighbors(u)


def test_dataset_volumes():
    ds = make_dataset("arxiv", scale=0.02, seed=1)
    assert ds.vol_f_bytes() > ds.vol_g_bytes()      # features dominate (Tab 2)
    assert ds.features.shape == (ds.num_vertices, 128)
    assert ds.train_vertices().size > 0


# ---------------------------------------------------------------------------
# Partitioners
# ---------------------------------------------------------------------------

@given(st.integers(16, 300), st.integers(2, 8), st.integers(0, 1000))
@settings(max_examples=25, deadline=None)
def test_partitioners_cover_every_vertex(n, parts, seed):
    for part in (hash_partition(n, parts, seed), range_partition(n, parts)):
        assert part.shape == (n,)
        assert part.min() >= 0 and part.max() < parts


def test_ldg_balanced_and_local(small_dataset):
    g = small_dataset.graph
    parts = 4
    part = ldg_partition(g, parts, passes=1)
    sizes = partition_sizes(part, parts)
    assert sizes.sum() == g.num_vertices
    assert sizes.max() <= 1.10 * g.num_vertices / parts   # capacity slack
    # locality: community graph must cut far fewer edges than random
    assert edge_cut(g, part) < 0.8 * edge_cut(
        g, hash_partition(g.num_vertices, parts, 0))


def test_shard_features_roundtrip(partitioned):
    ds, part = partitioned["ds"], partitioned["part"]
    table, owner, local_idx = (partitioned["table"], partitioned["owner"],
                               partitioned["local_idx"])
    v = np.arange(0, ds.num_vertices, 97)
    np.testing.assert_array_equal(table[owner[v], local_idx[v]],
                                  ds.features[v])
    o2, l2, mx = local_index_map(part, partitioned["parts"])
    assert table.shape[1] == mx


# ---------------------------------------------------------------------------
# Sampler
# ---------------------------------------------------------------------------

@given(st.integers(1, 16), st.integers(1, 3), st.integers(1, 5),
       st.integers(0, 99))
@settings(max_examples=20, deadline=None)
def test_tree_block_shapes(batch, layers, fanout, seed):
    ds = make_dataset("arxiv", scale=0.01, seed=0)
    rng = np.random.default_rng(seed)
    roots = rng.integers(0, ds.num_vertices, batch)
    blk = sample_tree_block(ds.graph, roots, layers, fanout, rng=rng)
    assert blk.num_layers == layers
    for h, ids in enumerate(blk.hops):
        assert ids.shape == (batch * fanout ** h,)
        assert ids.min() >= 0 and ids.max() < ds.num_vertices


def test_deterministic_sampling_is_per_root(small_dataset):
    """Stateless mode: a root's subtree is identical whether sampled alone
    or inside a batch — the gradient-parity prerequisite."""
    g = small_dataset.graph
    roots = np.array([5, 17, 42, 3])
    blk = sample_tree_block(g, roots, 2, 3, seed=11)
    for i, r in enumerate(roots):
        solo = sample_tree_block(g, np.array([r]), 2, 3, seed=11)
        sub = blk.select(np.array([i]))
        for h_solo, h_sub in zip(solo.hops, sub.hops):
            np.testing.assert_array_equal(h_solo, h_sub)


def test_sampler_modes_exclusive(small_dataset):
    g = small_dataset.graph
    with pytest.raises(ValueError):
        sample_tree_block(g, np.array([0]), 1, 2)
    with pytest.raises(ValueError):
        sample_tree_block(g, np.array([0]), 1, 2,
                          rng=np.random.default_rng(0), seed=1)


def test_micrograph_split_and_grouping(partitioned):
    ds, part = partitioned["ds"], partitioned["part"]
    rng = np.random.default_rng(0)
    roots = rng.integers(0, ds.num_vertices, 8)
    blk = sample_tree_block(ds.graph, roots, 2, 3, rng=rng)
    micros = micrograph_split(blk)
    assert len(micros) == 8
    assert all(m.batch_size == 1 for m in micros)
    groups = group_roots_by_home(roots, part, partitioned["parts"])
    assert sum(g.size for g in groups) == roots.size
    for s, grp in enumerate(groups):
        assert np.all(part[grp] == s)


def test_micrograph_locality_beats_subgraph(partitioned):
    """Table 1's central claim: R_micro > R_sub on a locality-partitioned
    graph."""
    ds, part = partitioned["ds"], partitioned["part"]
    rng = np.random.default_rng(3)
    roots = rng.choice(ds.num_vertices, 64, replace=False)
    blk = sample_tree_block(ds.graph, roots, 2, 5, rng=rng)
    r_micro = np.mean([m.locality(part) for m in micrograph_split(blk)])
    r_sub = blk.locality(part)       # vs the first root's home
    assert r_micro > r_sub
