"""The plan's deferred accounting: exact against the eager loop it
replaced, computed once on first read, and never computed by training."""
import sys
import threading

import numpy as np
import pytest

from repro.core import plan_iteration
from repro.core.strategies import _pad_tree_block
from repro.features import FeatureStore
from repro.graph.partition import drop_cross_edges
from repro.graph.sampler import sample_tree_block
from repro.models.gnn import GNNConfig
from repro.obs import metrics
from repro.optim import adam
from repro.train import Trainer

COUNTER = "planner.account_computed"
SEED = 7
LAYERS, FANOUT = 2, 4


def _computed() -> int:
    return metrics.registry().counter(COUNTER).value


def _roots(d, per_model=12, seed=0):
    rng = np.random.default_rng(seed)
    tv = d["ds"].train_vertices()
    return [rng.choice(tv, per_model, replace=False)
            for _ in range(d["parts"])]


def _plan(d, strategy="hopgnn", **kw):
    return plan_iteration(
        d["ds"].graph, d["ds"].labels, d["part"], d["owner"],
        d["local_idx"], d["table"].shape[1], _roots(d),
        num_layers=LAYERS, fanout=FANOUT, strategy=strategy,
        sample_seed=SEED, **kw)


def _eager_reference(d, plan, strategy):
    """The planner's former eager accounting: resample every (shard, step)
    block, pad it as the planner does, select its true roots back and dedup
    per (shard, step) and per shard."""
    graph = d["ds"].graph
    if strategy == "lo":
        graph = drop_cross_edges(graph, d["part"])
    owner, amat = d["owner"], plan.assignment
    n, T = plan.num_shards, plan.num_steps
    blocks, true_root_blocks = {}, []
    for s in range(n):
        for t in range(T):
            roots = amat.roots_at(s, t)
            blk = sample_tree_block(graph, roots, LAYERS, FANOUT, seed=SEED)
            blocks[s, t] = _pad_tree_block(blk, plan.batch_pad, 0)
            if roots.size:
                true_root_blocks.append(blk)
    total_rows = sum(b.num_feature_rows() for b in true_root_blocks)
    uniq_all = []
    remote_nodedup = 0
    step_unique = 0
    for s in range(n):
        per_step_ids = []
        for t in range(T):
            roots = amat.roots_at(s, t)
            if roots.size == 0:
                continue
            ids = blocks[s, t].select(np.arange(roots.size)).all_ids()
            per_step_ids.append(ids)
        if per_step_ids:
            allids = np.concatenate(per_step_ids)
            uniq_all.append(np.unique(allids))
            for ids in per_step_ids:
                u = np.unique(ids)
                step_unique += u.size
                remote_nodedup += int((owner[u] != s).sum())
    unique_rows = int(sum(u.size for u in uniq_all))
    return dict(total_rows=total_rows, unique_rows=unique_rows,
                step_unique_rows=step_unique,
                remote_rows_nodedup=remote_nodedup)


def _device_arrays(plan):
    arrs = [plan.req, plan.labels, plan.weights, *plan.hop_idx]
    if plan.step_req is not None:
        arrs.append(plan.step_req)
    return [a.copy() for a in arrs]


CASES = [(s, p, False) for s in ("hopgnn", "model_centric", "lo")
         for p in (True, False)] + [("hopgnn", True, True)]


@pytest.mark.parametrize("strategy,pregather,streamed", CASES)
def test_deferred_accounting_equals_eager(partitioned, strategy, pregather,
                                          streamed):
    d = partitioned
    kw = {}
    if streamed:
        kw["feature_store"] = FeatureStore.from_array(
            d["table"], host_budget_bytes=max(1, d["table"].nbytes // 3))
    plan = _plan(d, strategy, pregather=pregather, **kw)
    assert plan.streamed == streamed
    before = _device_arrays(plan)
    ref = _eager_reference(d, plan, strategy)
    assert ref["unique_rows"] > 0
    for name, want in ref.items():
        assert getattr(plan, name) == want, name
    assert plan.miss_rate() == (plan.remote_rows_exact
                                / max(ref["unique_rows"], 1))
    assert plan.miss_rate_per_request() == (
        ref["remote_rows_nodedup"] / max(ref["step_unique_rows"], 1))
    for a, b in zip(before, _device_arrays(plan)):
        np.testing.assert_array_equal(a, b)


def test_accounting_is_deferred_and_computed_once(partitioned):
    start = _computed()
    plan = _plan(partitioned)
    assert _computed() == start
    assert plan.total_rows > 0
    assert _computed() == start
    first = plan.unique_rows
    assert _computed() == start + 1
    names = ("total_rows", "unique_rows", "step_unique_rows",
             "remote_rows_nodedup")
    reads = [[getattr(plan, k) for k in names] for _ in range(2)]
    plan.miss_rate(), plan.miss_rate_per_request()
    assert reads[0] == reads[1] and reads[0][1] == first
    assert _computed() == start + 1


def test_accounting_computes_once_across_threads(partitioned):
    plan = _plan(partitioned, pregather=False)
    start = _computed()
    barrier = threading.Barrier(8)
    got = []

    def read():
        barrier.wait()
        got.append((plan.unique_rows, plan.step_unique_rows,
                    plan.remote_rows_nodedup))

    threads = [threading.Thread(target=read) for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    assert len(got) == 8 and len(set(got)) == 1
    assert _computed() == start + 1


def test_training_never_computes_accounting(partitioned):
    d = partitioned
    cfg = GNNConfig(model="sage", num_layers=2, hidden_dim=16,
                    feature_dim=d["ds"].feature_dim,
                    num_classes=d["ds"].num_classes, fanout=FANOUT)
    tr = Trainer(graph=d["ds"].graph, labels=d["ds"].labels, part=d["part"],
                 owner=d["owner"], local_idx=d["local_idx"],
                 table=d["table"], cfg=cfg, optimizer=adam(5e-3),
                 merging=False, train_vertices=d["ds"].train_vertices())
    start = _computed()
    stats = tr.fit(epochs=1, iters_per_epoch=3, batch_per_model=8)
    assert stats and np.isfinite(stats[-1].loss)
    assert _computed() == start
