"""repro.serve benchmark: online-inference latency/throughput + gates.

Four CI-facing contracts (BENCH_serve.json, repo root):

1. **Parity** (hard gate) — served logits bitwise-equal to the compiled
   offline eval program at the same padded rung for a probe set, with the
   hot-feature cache enabled and refreshing mid-run. Exact 0/1.
2. **Retraces** (hard gate) — steady-state serving compiles nothing after
   :meth:`GNNServer.warmup`; the engine trace log's ``infer`` count must
   not move across the whole bench. Exact 0.
3. **Dynamic batching** (timing gate, retried once in CI) — at
   saturation (closed-loop burst) the dynamic micro-batcher must beat
   batch-size-1 serving by ≥ 2× on throughput.
4. **Latency curve** — open-loop offered-QPS sweep at 3 levels below
   saturation, reporting served p50/p99 ms per level (the user-visible
   latency semantics: submit → result, queue wait included).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import Bench, setup
from repro.core.distributed import infer_trace_count
from repro.features import FeatureStore
from repro.serve import GNNServer
from repro.train.budget import ShapeBudget, next_bucket

SPEEDUP_GATE_X = 2.0
QPS_FRACTIONS = (0.25, 0.5, 0.8)


def _make_server(env, cfg, params, store, *, max_batch, budget=None):
    return GNNServer(graph=env["ds"].graph, params=params, cfg=cfg,
                     store=store, budget=budget, max_batch=max_batch,
                     cache_budget_bytes=1 << 20, cache_refresh_every=8)


def _drain_burst(srv, nodes) -> float:
    """Closed-loop: enqueue everything, pump to empty. Returns seconds."""
    t0 = time.perf_counter()
    tickets = [srv.submit(int(v)) for v in nodes]
    while not all(t.done() for t in tickets):
        srv.loop.pump(wait_s=0.0)
    return time.perf_counter() - t0


def _offered_sweep(srv, nodes, offered_qps) -> dict:
    """Open-loop: background serving thread, client paces submissions at
    ``offered_qps``; latency is submit → result per ticket."""
    srv.start()
    try:
        gap = 1.0 / offered_qps
        tickets = []
        t_next = time.perf_counter()
        for v in nodes:
            now = time.perf_counter()
            if now < t_next:
                time.sleep(t_next - now)
            tickets.append(srv.submit(int(v)))
            t_next += gap
        t0 = time.perf_counter()
        for t in tickets:
            t.wait(120.0)
        lat_ms = np.array([1e3 * t.latency_s() for t in tickets])
        span = max(tickets[-1].t_done - tickets[0].t_submit, 1e-9)
        return {"achieved_qps": round(len(tickets) / span, 1),
                "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                "p99_ms": round(float(np.percentile(lat_ms, 99)), 3)}
    finally:
        srv.stop()


def run(quick: bool = True) -> Bench:
    import jax
    from benchmarks.common import gnn_cfg
    from repro.models.gnn import init_gnn

    b = Bench("serve")
    env = setup(dataset="products", scale=0.02, parts=4,
                partitioner="community", seed=0)
    store = FeatureStore.from_array(env["table"], owner=env["owner"],
                                    local_idx=env["local_idx"])
    cfg = gnn_cfg("sage", env, hidden=32)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    n = env["ds"].graph.num_vertices
    burst_n = 128 if quick else 256
    sweep_n = 60 if quick else 150

    # both servers warm up (compile their rungs) before the retrace
    # baseline is taken — everything after this line must compile nothing
    dyn = _make_server(env, cfg, params, store, max_batch=64)
    dyn.warmup()
    b1 = _make_server(env, cfg, params, store, max_batch=1,
                      budget=ShapeBudget(min_batch_pad=1))
    b1.warmup()
    traces_baseline = infer_trace_count()

    # ---- dynamic batcher at saturation ---------------------------------
    burst = rng.integers(0, n, burst_n)
    dyn_s = _drain_burst(dyn, burst)
    dyn_qps = burst_n / dyn_s
    b.emit("saturation", "dyn_qps", round(dyn_qps, 1))
    b.emit("saturation", "dyn_mean_batch",
           round(dyn.loop.served / max(dyn.loop.batches, 1), 1))

    # ---- batch-size-1 baseline (honest: batch_pad rung of 1) -----------
    b1_s = _drain_burst(b1, burst)
    b1_qps = burst_n / b1_s
    speedup = dyn_qps / b1_qps
    b.emit("saturation", "b1_qps", round(b1_qps, 1))
    b.emit("saturation", "speedup_x", round(speedup, 2))
    b.emit("saturation", "meets_2x_gate", int(speedup >= SPEEDUP_GATE_X))

    # ---- offered-QPS sweep (open loop, 3 levels below saturation) ------
    for frac in QPS_FRACTIONS:
        offered = max(dyn_qps * frac, 1.0)
        nodes = rng.integers(0, n, sweep_n)
        res = _offered_sweep(dyn, nodes, offered)
        case = f"qps_{frac}"
        b.emit(case, "offered_qps", round(offered, 1))
        for k, v in res.items():
            b.emit(case, k, v)

    # ---- hard gates: parity + compile-once -----------------------------
    import jax.numpy as jnp
    from repro.core import get_compiled_inference, plan_inference
    probe = np.unique(rng.integers(0, n, 32))
    got = dyn.predict(probe.tolist())
    # read before the reference below compiles a program of its own
    retraces = infer_trace_count() - traces_baseline
    # the offline eval program: no cache, features straight from the store,
    # at the rung the one-micro-batch probe was served at
    plan = plan_inference(env["ds"].graph, probe, cfg.num_layers, cfg.fanout,
                          sample_seed=999, batch_pad=next_bucket(
                              probe.size, dyn.budget.min_batch_pad))
    fetched = jnp.asarray(store.take_global(plan.fetch_ids))
    ref = np.asarray(get_compiled_inference(cfg)(
        params, jnp.zeros((0, store.feature_dim), fetched.dtype), fetched,
        *[jnp.asarray(h) for h in plan.hop_idx]))[:probe.size]
    b.emit("parity", "bitwise_equal", int(np.array_equal(got, ref)))
    b.emit("retraces", "after_warmup", retraces)
    b.emit("retraces", "cache_installs", dyn.stats()["cache_installs"])

    b.save_csv()
    b.save_json(seed=0)
    return b


if __name__ == "__main__":
    run(quick=True)
