"""repro.serve: queue/micro-batcher units, tiered GNN server parity
(served == compiled offline eval program, bitwise; == eager forward up to
float32 rounding), compile-once steady state, precomputed-embedding tier,
obs wiring, LLM loop unification."""
import threading

import numpy as np
import pytest

from repro.core import plan_inference
from repro.core.distributed import infer_trace_count
from repro.features import FeatureStore
from repro.serve import (BatchingLoop, GNNServer, RequestQueue,
                         load_embeddings, precompute_embeddings)
from repro.train.budget import ShapeBudget, next_bucket


# ----------------------------------------------------------------------
# Shared serving fixture: model + bound store over the session partition
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def served(partitioned):
    import jax
    from repro.models.gnn.models import GNNConfig, init_gnn
    ds = partitioned["ds"]
    store = FeatureStore.from_array(partitioned["table"],
                                    owner=partitioned["owner"],
                                    local_idx=partitioned["local_idx"])
    cfg = GNNConfig(model="sage", feature_dim=ds.features.shape[1],
                    hidden_dim=32, num_classes=int(ds.labels.max()) + 1,
                    num_layers=2, fanout=10)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    return dict(ds=ds, store=store, cfg=cfg, params=params)


def offline_logits(served, nodes, batch_pad):
    """The bitwise parity reference: the offline eval program — the
    compiled serving forward (``get_compiled_inference``, which
    ``precompute_embeddings`` also runs) on the eval-seed tree block, its
    features gathered straight from the store (no cache, no precomputed
    tier), padded to ``batch_pad`` roots. XLA compiles one program per
    padded height and their float32 results can differ in the last bit,
    so bitwise parity holds at the rung the served answer came from."""
    import jax.numpy as jnp
    from repro.core.distributed import get_compiled_inference
    cfg, store = served["cfg"], served["store"]
    nodes = np.asarray(nodes, np.int64)
    plan = plan_inference(served["ds"].graph, nodes, cfg.num_layers,
                          cfg.fanout, sample_seed=999, batch_pad=batch_pad)
    fetched = jnp.asarray(store.take_global(plan.fetch_ids))
    empty = jnp.zeros((0, store.feature_dim), fetched.dtype)
    logits = get_compiled_inference(cfg)(
        served["params"], empty, fetched,
        *[jnp.asarray(h) for h in plan.hop_idx])
    return np.asarray(logits)[:nodes.size]


def eager_logits(served, nodes):
    """Trainer.evaluate's eager forward: an independent oracle, equal to
    the served logits up to float32 rounding."""
    import jax.numpy as jnp
    from repro.graph.sampler import sample_tree_block
    from repro.models.gnn.models import gnn_forward
    cfg = served["cfg"]
    blk = sample_tree_block(served["ds"].graph,
                            np.asarray(nodes, np.int64),
                            cfg.num_layers, cfg.fanout, seed=999)
    feats = [jnp.asarray(served["store"].take_global(ids))
             for ids in blk.hops]
    return np.asarray(gnn_forward(served["params"], cfg, feats))


def assert_served(out, served, nodes, batch_pad):
    """served ≡ offline eval program (bitwise), ≈ eager forward."""
    assert np.array_equal(out, offline_logits(served, nodes, batch_pad))
    np.testing.assert_allclose(out, eager_logits(served, nodes),
                               rtol=1e-5, atol=1e-5)


def fresh_rung(srv, nodes) -> int:
    """The padded batch a one-micro-batch fresh compute of ``nodes`` ran
    at (``ShapeBudget.serve_batch_pad``)."""
    return next_bucket(len(set(nodes)), srv.budget.min_batch_pad)


def make_server(served, **kw):
    return GNNServer(graph=served["ds"].graph, params=served["params"],
                     cfg=served["cfg"], store=served["store"], **kw)


# ----------------------------------------------------------------------
# Queue / micro-batcher units
# ----------------------------------------------------------------------

def test_queue_fifo_and_batching():
    q = RequestQueue()
    tickets = [q.put(i) for i in range(7)]
    assert q.depth() == 7
    first = q.drain(4)
    assert [t.payload for t in first] == [0, 1, 2, 3]
    assert [t.payload for t in q.drain(100)] == [4, 5, 6]
    assert q.drain(4, wait_s=0.0) == []
    assert all(t.t_drain >= t.t_submit for t in tickets)


def test_loop_dispatch_results_and_errors():
    calls = []

    def dispatch(ts):
        calls.append(len(ts))
        if any(t.payload == "boom" for t in ts):
            raise RuntimeError("boom")
        return [t.payload * 2 for t in ts]

    loop = BatchingLoop(dispatch, max_batch=3, name="tloop")
    ts = [loop.submit(i) for i in range(5)]
    assert loop.pump(wait_s=0.0) == 3
    assert loop.pump(wait_s=0.0) == 2
    assert [t.wait(1.0) for t in ts] == [0, 2, 4, 6, 8]
    assert calls == [3, 2]

    bad = loop.submit("boom")
    with pytest.raises(RuntimeError):
        loop.pump(wait_s=0.0)
    with pytest.raises(RuntimeError):
        bad.wait(1.0)
    assert loop.errors == 1
    # the loop keeps serving after a failed batch
    ok = loop.submit(10)
    loop.pump(wait_s=0.0)
    assert ok.wait(1.0) == 20


def test_loop_background_thread():
    loop = BatchingLoop(lambda ts: [t.payload + 1 for t in ts],
                        max_batch=8, name="bg")
    loop.start()
    try:
        results = []

        def client():
            results.extend(loop.submit(i).wait(10.0) for i in range(20))

        th = threading.Thread(target=client)
        th.start()
        th.join(30.0)
        assert results == list(range(1, 21))
    finally:
        loop.stop()
    assert loop.served == 20


# ----------------------------------------------------------------------
# Inference planner
# ----------------------------------------------------------------------

def test_plan_inference_shapes(partitioned):
    g = partitioned["ds"].graph
    nodes = np.array([5, 9, 21], np.int64)
    plan = plan_inference(g, nodes, 2, 10, sample_seed=999, batch_pad=8)
    assert plan.batch_pad == 8 and plan.num_layers == 2
    assert plan.hop_idx[0].size == 8
    assert plan.hop_idx[1].size == 80
    assert plan.hop_idx[2].size == 800
    # no cache: workspace is exactly the fetched uniques
    fetched = np.sort(plan.fetch_ids)
    assert np.array_equal(plan.fetch_ids, fetched)
    for h in plan.hop_idx:
        assert h.min() >= 0 and h.max() < plan.fetch_ids.size
    # determinism: same roots, same seed → identical plan
    plan2 = plan_inference(g, nodes, 2, 10, sample_seed=999, batch_pad=8)
    assert np.array_equal(plan.fetch_ids, plan2.fetch_ids)
    assert all(np.array_equal(a, b)
               for a, b in zip(plan.hop_idx, plan2.hop_idx))


def test_plan_inference_overflow(partitioned):
    from repro.core import PlanOverflow
    g = partitioned["ds"].graph
    with pytest.raises(PlanOverflow):
        plan_inference(g, np.arange(9), 2, 10, sample_seed=999, batch_pad=8)


# ----------------------------------------------------------------------
# Serving parity: served == offline eval forward, bitwise
# ----------------------------------------------------------------------

def test_parity_cache_off(served):
    srv = make_server(served)
    srv.warmup()
    nodes = [3, 14, 15, 92, 65, 35]
    out = srv.predict(nodes)
    assert_served(out, served, nodes, fresh_rung(srv, nodes))


def test_parity_cache_on_across_installs(served):
    srv = make_server(served, cache_budget_bytes=256 * 1024,
                      cache_refresh_every=2)
    srv.warmup()
    rng = np.random.default_rng(1)
    n = served["ds"].graph.num_vertices
    served_batches = []
    for _ in range(8):
        nodes = np.unique(rng.integers(0, n, 12))
        served_batches.append((nodes, srv.predict(nodes.tolist())))
    st = srv.stats()
    assert st["cache_installs"] > 0, "cache never admitted anything"
    assert st["cache_hit_rows"] > 0, "admitted rows never hit"
    assert srv.retraces_since_warmup == 0
    # the references compile programs of their own: check them after the
    # retrace gate
    for i, (nodes, out) in enumerate(served_batches):
        assert np.array_equal(
            out, offline_logits(served, nodes, fresh_rung(srv, nodes))), \
            f"parity broke at batch {i} (installs={st['cache_installs']})"
        np.testing.assert_allclose(out, eager_logits(served, nodes),
                                   rtol=1e-5, atol=1e-5)


def test_parity_streamed_store(served, tmp_path):
    """Same contract when features resolve through the tiered (host hot
    tier → mmap disk) store rather than a resident table."""
    ds = served["ds"]
    # rebuild a spilled store over the same partition
    from repro.graph import ldg_partition
    part = ldg_partition(ds.graph, 4, passes=1)
    streamed = FeatureStore.build(ds.features, part, 4,
                                  directory=tmp_path / "feats",
                                  host_budget_bytes=64 * 1024)
    assert not streamed.resident
    srv = GNNServer(graph=ds.graph, params=served["params"],
                    cfg=served["cfg"], store=streamed,
                    cache_budget_bytes=128 * 1024)
    srv.warmup()
    nodes = [7, 11, 200, 41]
    out = srv.predict(nodes)
    assert_served(out, served, nodes, fresh_rung(srv, nodes))


def test_dense_array_store(served):
    """A raw (N, d) table is accepted and serves identically."""
    srv = make_server(served)
    srv.warmup()
    dense = GNNServer(graph=served["ds"].graph, params=served["params"],
                      cfg=served["cfg"], store=served["ds"].features)
    dense.warmup()
    nodes = [3, 14, 15]
    assert np.array_equal(dense.predict(nodes), srv.predict(nodes))


# ----------------------------------------------------------------------
# Compile-once steady state
# ----------------------------------------------------------------------

def test_zero_retraces_after_warmup(served):
    srv = make_server(served, cache_budget_bytes=256 * 1024,
                      cache_refresh_every=3, max_batch=16)
    srv.warmup()
    before = infer_trace_count()
    rng = np.random.default_rng(2)
    n = served["ds"].graph.num_vertices
    for _ in range(25):
        k = int(rng.integers(1, 17))
        srv.predict(rng.integers(0, n, k).tolist())
    assert infer_trace_count() == before, \
        "steady-state serving retraced after warmup"
    assert srv.retraces_since_warmup == 0
    assert srv.stats()["cache_installs"] > 0  # installs didn't retrace


def test_budget_serve_buckets_roundtrip():
    b = ShapeBudget()
    bp = b.serve_batch_pad(13)
    assert bp == 16
    u = b.serve_fetch_pad(bp, 700)
    assert u >= 700 and (u & (u - 1)) == 0
    # growth re-buckets; shrink keeps the learned rung
    assert b.serve_fetch_pad(bp, u + 1) > u
    assert b.serve_fetch_pad(bp, 8) == b.serve_fetch_pad(bp, 8)
    b2 = ShapeBudget()
    b2.load_state(b.state_dict())
    assert b2.serve_rungs() == b.serve_rungs()


# ----------------------------------------------------------------------
# Precomputed-embedding tier
# ----------------------------------------------------------------------

def test_precomputed_tier_parity_and_staleness(served, tmp_path):
    ds, cfg = served["ds"], served["cfg"]
    precompute_embeddings(ds.graph, served["store"], served["params"], cfg,
                          ckpt_dir=tmp_path, params_step=7, chunk=128)
    tab = load_embeddings(tmp_path, params_step=7, sample_seed=999)
    assert tab.num_vertices == ds.graph.num_vertices
    nodes = [3, 14, 15, 92]
    # the precompute ran every vertex at the chunk's rung
    assert_served(tab.lookup(nodes), served, nodes, 128)

    # serving from the table alone: bit-identical, zero fresh computes
    srv = make_server(served, ckpt_dir=tmp_path, params_step=7,
                      mode="precomputed")
    out = srv.predict(nodes)
    assert np.array_equal(out, tab.lookup(nodes))
    assert srv.fresh_batches == 0 and srv.precomputed_hits == len(nodes)

    # stale stamps are refused...
    with pytest.raises(ValueError, match="stale"):
        load_embeddings(tmp_path, params_step=8)
    with pytest.raises(ValueError, match="seed"):
        load_embeddings(tmp_path, params_step=7, sample_seed=123)
    with pytest.raises(FileNotFoundError):
        load_embeddings(tmp_path / "nowhere")
    # ...unless explicitly allowed
    assert load_embeddings(tmp_path, params_step=8,
                           allow_stale=True).num_vertices


def test_auto_mode_promotes_hot_vertices(served, tmp_path):
    ds = served["ds"]
    precompute_embeddings(ds.graph, served["store"], served["params"],
                          served["cfg"], ckpt_dir=tmp_path, params_step=0)
    srv = make_server(served, ckpt_dir=tmp_path, params_step=0, mode="auto",
                      cache_budget_bytes=256 * 1024, cache_refresh_every=1)
    srv.warmup()
    fresh_after_warmup = srv.fresh_batches
    # cold vertex → precomputed tier
    t = srv.submit(42)
    srv.loop.pump(wait_s=0.0)
    t.wait(1.0)
    assert t.via == "precomputed"
    assert srv.fresh_batches == fresh_after_warmup
    # hammer the same vertex: LFU admits its feature row, later requests
    # flip to fresh compute (current-params answers at cached-feature cost)
    for _ in range(6):
        srv.predict([42])
    t2 = srv.submit(42)
    srv.loop.pump(wait_s=0.0)
    t2.wait(1.0)
    assert t2.via == "fresh"
    assert_served(t2.result[None], served, [42], fresh_rung(srv, [42]))


def test_edge_prediction(served):
    srv = make_server(served)
    srv.warmup()
    t = srv.submit((3, 14))
    srv.loop.pump(wait_s=0.0)
    score = t.wait(1.0)
    ref = eager_logits(served, [3, 14])
    assert score == pytest.approx(float(np.dot(ref[0], ref[1])))
    assert t.via == "edge"


# ----------------------------------------------------------------------
# Observability wiring
# ----------------------------------------------------------------------

def test_serve_spans_and_metrics(served):
    from repro.obs import metrics, trace
    srv = make_server(served, cache_budget_bytes=128 * 1024)
    srv.warmup()
    trace.enable()
    try:
        srv.predict([3, 14, 15])
        names = {r.name for r in trace.records()}
    finally:
        trace.disable()
    for want in ("serve.queue.wait", "serve.batch", "serve.batch.build",
                 "serve.dispatch", "serve.sync"):
        assert want in names, f"missing span {want} (got {sorted(names)})"
    snap = metrics.registry().snapshot()
    flat = {k for section in snap.values() if isinstance(section, dict)
            for k in section}
    for want in ("serve.requests", "serve.batches", "serve.latency_ms",
                 "serve.queue_wait_ms", "serve.queue_depth", "serve.qps"):
        assert want in flat, f"missing metric {want}"


# ----------------------------------------------------------------------
# LLM unification: same loop, transformer dispatch
# ----------------------------------------------------------------------

def test_llm_server_smoke():
    import jax
    from repro.configs import get_config, smoke_variant
    from repro.launch.serve import LLMServer
    from repro.models.transformer import init_params
    cfg = smoke_variant(get_config("qwen2-1.5b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    srv = LLMServer(params, cfg, gen_tokens=4, max_batch=4, name="llm")
    rng = np.random.default_rng(0)
    ts = [srv.submit(rng.integers(1, cfg.vocab_size, 8)) for _ in range(5)]
    while not all(t.done() for t in ts):
        srv.pump(wait_s=0.0)
    for t in ts:
        out = t.wait(1.0)
        assert out.shape == (4,) and out.dtype == np.int32
    st = srv.stats()
    assert st["served"] == 5 and st["batches"] >= 2 and st["errors"] == 0
