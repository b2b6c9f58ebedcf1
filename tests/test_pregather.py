"""Vectorized planner ≡ reference planner (the tentpole parity invariant).

The vectorized ``build_gather_plan`` (bitmap/sort dedup + SlotMap) must
produce byte-identical plans to the kept per-vertex reference
implementation: same req layout, same counts, same slot assignments, same
overflow behavior — on random graphs, through both dedup paths, and for
the hop translation. Property-tested via hypothesis, plus a seeded sweep.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.core.pregather as pg
from repro.core.pregather import (SlotMap, _reference_build_gather_plan,
                                  _reference_workspace_indices,
                                  build_gather_plan, workspace_indices)


def _random_partition(rng, n_shards, n_vertices):
    owner = rng.integers(0, n_shards, n_vertices).astype(np.int32)
    local_idx = np.zeros(n_vertices, np.int32)
    for s in range(n_shards):
        ids = np.nonzero(owner == s)[0]
        local_idx[ids] = np.arange(ids.size)
    local_rows = max(1, int(np.bincount(owner, minlength=n_shards).max()))
    return owner, local_idx, local_rows


def _assert_plans_equal(a, b):
    np.testing.assert_array_equal(a.req, b.req)
    np.testing.assert_array_equal(a.req_count, b.req_count)
    assert a.r_max == b.r_max
    np.testing.assert_array_equal(a.slot_map.starts, b.slot_map.starts)
    np.testing.assert_array_equal(a.slot_map.ids, b.slot_map.ids)
    np.testing.assert_array_equal(a.slot_map.slots, b.slot_map.slots)


def _check_case(n_shards, n_vertices, n_ids, seed, r_max=None):
    rng = np.random.default_rng(seed)
    owner, local_idx, local_rows = _random_partition(rng, n_shards,
                                                     n_vertices)
    needed = [rng.integers(0, n_vertices, n_ids) for _ in range(n_shards)]
    try:
        a = build_gather_plan(needed, owner, local_idx, n_shards,
                              local_rows, r_max)
        overflow_a = None
    except pg.PlanOverflow as e:
        a, overflow_a = None, e
    try:
        b = _reference_build_gather_plan(needed, owner, local_idx, n_shards,
                                         local_rows, r_max)
        overflow_b = None
    except pg.PlanOverflow as e:
        b, overflow_b = None, e
    if overflow_a or overflow_b:
        # both must overflow, identically
        assert overflow_a is not None and overflow_b is not None
        assert (overflow_a.field, overflow_a.needed, overflow_a.limit) == \
            (overflow_b.field, overflow_b.needed, overflow_b.limit)
        return
    _assert_plans_equal(a, b)
    # hop translation parity (exercises translation_row + lookup oracle)
    for s in range(n_shards):
        if needed[s].size == 0:
            continue
        hops = [needed[s][rng.integers(0, needed[s].size, 64)],
                needed[s][rng.integers(0, needed[s].size, 2048)]]
        wa = workspace_indices(hops, s, owner, local_idx, a)
        wb = _reference_workspace_indices(hops, s, owner, local_idx, b)
        for x, y in zip(wa, wb):
            np.testing.assert_array_equal(x, y)


@given(st.integers(2, 8), st.integers(8, 400), st.integers(0, 120),
       st.integers(0, 10_000))
@settings(max_examples=30, deadline=None)
def test_planner_parity_property(n_shards, n_vertices, n_ids, seed):
    """Vectorized planner ≡ reference planner on random graphs."""
    _check_case(n_shards, n_vertices, n_ids, seed)


@pytest.mark.parametrize("seed", range(12))
@pytest.mark.parametrize("dense", [True, False])
def test_planner_parity_seeded(seed, dense, monkeypatch):
    """Always-on parity sweep through BOTH dedup paths (the bitmap path
    and the sort fallback the memory guard selects at scale)."""
    monkeypatch.setattr(pg, "_DENSE_DEDUP_MAX_CELLS",
                        (1 << 28) if dense else 0)
    rng = np.random.default_rng(1000 + seed)
    _check_case(int(rng.integers(2, 8)), int(rng.integers(8, 400)),
                int(rng.integers(0, 120)), seed)


@pytest.mark.parametrize("dense", [True, False])
def test_planner_parity_with_budgeted_r_max(dense, monkeypatch):
    monkeypatch.setattr(pg, "_DENSE_DEDUP_MAX_CELLS",
                        (1 << 28) if dense else 0)
    _check_case(4, 200, 80, seed=3, r_max=64)       # roomy bucket
    _check_case(4, 200, 80, seed=3, r_max=1)        # must overflow both


def test_slotmap_lookup_rejects_unknown_ids():
    rng = np.random.default_rng(0)
    owner, local_idx, local_rows = _random_partition(rng, 3, 50)
    needed = [rng.integers(0, 50, 20) for _ in range(3)]
    plan = build_gather_plan(needed, owner, local_idx, 3, local_rows)
    local_ids = np.nonzero(owner == 0)[0][:1]
    with pytest.raises(KeyError):
        plan.slot_map.lookup(0, local_ids)          # local id: never remote
    # a shard with an EMPTY remote set must also raise KeyError (not
    # IndexError from probing a zero-length segment)
    empty = build_gather_plan([np.zeros(0, np.int64)] * 3, owner,
                              local_idx, 3, local_rows)
    with pytest.raises(KeyError):
        empty.slot_map.lookup(0, local_ids)


def test_slotmap_translation_row_covers_local_and_remote():
    rng = np.random.default_rng(4)
    owner, local_idx, local_rows = _random_partition(rng, 4, 120)
    needed = [rng.integers(0, 120, 60) for _ in range(4)]
    plan = build_gather_plan(needed, owner, local_idx, 4, local_rows)
    for s in range(4):
        row = plan.slot_map.translation_row(s, owner, local_idx)
        assert row is not None and row.dtype == np.int32
        local = np.nonzero(owner == s)[0]
        np.testing.assert_array_equal(row[local], local_idx[local])
        remote = plan.slot_map.shard_ids(s)
        np.testing.assert_array_equal(row[remote],
                                      plan.slot_map.shard_slots(s))
        untouched = np.setdiff1d(np.arange(120),
                                 np.concatenate([local, remote]))
        assert np.all(row[untouched] == -1)


def test_slotmap_shard_segments_sorted():
    rng = np.random.default_rng(5)
    owner, local_idx, local_rows = _random_partition(rng, 5, 300)
    needed = [rng.integers(0, 300, 100) for _ in range(5)]
    plan = build_gather_plan(needed, owner, local_idx, 5, local_rows)
    sm: SlotMap = plan.slot_map
    for s in range(5):
        ids = sm.shard_ids(s)
        assert np.all(np.diff(ids) > 0)             # strictly sorted, unique
        # slot layout invariant: slot = local_rows + p*r_max + j
        slots = sm.shard_slots(s)
        assert np.all(slots >= local_rows)
        assert np.all(slots < local_rows + 5 * plan.r_max)
