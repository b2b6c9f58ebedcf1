"""repro.obs: span tracing, unified metrics registry, Perfetto export.

* recorder mechanics — nesting depth, disabled fast path, ring
  wraparound + dropped accounting, per-thread tracks, track override,
* registry — counter/gauge/histogram snapshot/delta (incl. instruments
  created after the snapshot),
* export — chrome-trace schema validity, manifests, JSONL round-trip,
* the training stack — bit-parity with tracing on (params + losses),
  lenient overhead bound (the strict 1.05x gate lives in
  benchmarks/obs.py, CI-gated), four-track + span coverage of a
  pipelined + cached run, EpochStats publication, TierStats/registry
  write-through, fault marks in the exported timeline.

The registry is process-global and cumulative across the suite, so all
assertions here are delta-based. Under the chaos lane
(REPRO_CHAOS_SEED) background faults add their own marks and retries —
tests assert presence, never absence.
"""
import json
import threading
import time

import jax
import numpy as np
import pytest

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.features import FeatureStore
from repro.models.gnn import GNNConfig
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.export import (chrome_trace, config_digest, run_manifest,
                              trace_span_names, trace_track_names,
                              validate_chrome_trace, write_metrics_jsonl)
from repro.optim import adam
from repro.resilience import (FaultPlan, FaultSpec, RetryPolicy,
                              TransientCommError, resilient_call)
from repro.train import Trainer


@pytest.fixture(autouse=True)
def _trace_reset():
    """Tracing state is module-global: leave every test with the
    recorder off and drained so tier-1 neighbours see the seed state."""
    yield
    obs_trace.disable()
    obs_trace.clear()


def _cfg(d):
    return GNNConfig(model="sage", num_layers=2, hidden_dim=16,
                     feature_dim=d["ds"].feature_dim,
                     num_classes=d["ds"].num_classes, fanout=4)


def _trainer(d, cfg, **kw):
    kw.setdefault("optimizer", adam(5e-3))
    kw.setdefault("merging", False)
    kw.setdefault("train_vertices", d["ds"].train_vertices())
    kw.setdefault("table", d["table"])
    return Trainer(graph=d["ds"].graph, labels=d["ds"].labels,
                   part=d["part"], owner=d["owner"],
                   local_idx=d["local_idx"], cfg=cfg, **kw)


# ---------------------------------------------------------------------------
# Recorder mechanics
# ---------------------------------------------------------------------------

def test_span_nesting_depth_and_order():
    obs_trace.enable()
    with obs_trace.span("outer", epoch=0):
        with obs_trace.span("inner"):
            pass
    recs = obs_trace.records()
    assert [r.name for r in recs] == ["outer", "inner"]
    outer, inner = recs
    assert outer.depth == 0 and inner.depth == 1
    assert outer.t0_ns <= inner.t0_ns and inner.t1_ns <= outer.t1_ns
    assert outer.tags == {"epoch": 0} and inner.tags is None
    assert outer.kind == "X" and outer.dur_ns >= 0


def test_disabled_is_shared_noop():
    obs_trace.disable()
    obs_trace.clear()
    s1 = obs_trace.span("hot", it=3)
    s2 = obs_trace.span("other")
    assert s1 is s2                       # one shared object, no alloc
    with s1:
        obs_trace.event("mark")
    assert obs_trace.records() == []
    assert not obs_trace.is_enabled()


def test_ring_wraparound_reports_dropped():
    obs_trace.enable(capacity=8)
    for i in range(20):
        obs_trace.event("e", idx=i)
    recs = obs_trace.records()
    assert len(recs) == 8                 # oldest overwritten, newest kept
    assert [r.tags["idx"] for r in recs] == list(range(12, 20))
    assert obs_trace.dropped() == 12


def test_clear_drops_records_keeps_state():
    obs_trace.enable()
    obs_trace.event("before")
    obs_trace.clear()
    assert obs_trace.records() == []
    assert obs_trace.is_enabled()
    obs_trace.event("after")
    assert [r.name for r in obs_trace.records()] == ["after"]


def test_threaded_recording_is_lossless_per_track():
    obs_trace.enable(capacity=4096)
    n_threads, n_spans = 4, 200

    def work(i):
        for k in range(n_spans):
            with obs_trace.span(f"w{i}", idx=k):
                pass

    ts = [threading.Thread(target=work, args=(i,), name=f"tsworker-{i}")
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    recs = obs_trace.records()
    for i in range(n_threads):
        mine = [r for r in recs if r.name == f"w{i}"]
        assert len(mine) == n_spans
        assert {r.track for r in mine} == {f"tsworker-{i}"}
    assert obs_trace.dropped() == 0


def test_track_override_records_virtual_lane():
    obs_trace.enable()
    with obs_trace.span("upload.commit", track="uploader", it=1):
        pass
    (rec,) = obs_trace.records()
    assert rec.track == "uploader"        # not MainThread
    doc = chrome_trace()
    assert "uploader" in trace_track_names(doc)


def test_span_cpu_time_within_wall():
    """cpu_ns is the thread's CPU time inside the span: near the wall time
    of a busy span, near zero for a sleeping one, never above the wall."""
    obs_trace.enable()
    with obs_trace.span("busy"):
        t = time.thread_time()
        while time.thread_time() - t < 0.02:
            pass
    with obs_trace.span("asleep"):
        time.sleep(0.02)
    obs_trace.event("mark")
    busy, asleep, mark = obs_trace.records()
    for r in (busy, asleep):
        assert 0 <= r.cpu_ns <= r.dur_ns
    assert busy.cpu_ns >= 0.02e9 and asleep.cpu_ns < 0.5 * asleep.dur_ns
    assert mark.cpu_ns is None


def test_spans_land_in_the_profiler_trace(tmp_path):
    """While enabled, every span is also a profiler annotation of its name,
    on the thread that records it."""
    from jax.profiler import ProfileData
    obs_trace.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.span("outer.span"):
            th = threading.Thread(
                target=lambda: obs_trace.span("pool.span").__enter__()
                .__exit__(None, None, None), name="plan_0")
            th.start()
            th.join()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events}
    assert {"outer.span", "pool.span"} <= names


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_registry_snapshot_delta():
    reg = obs_metrics.registry()
    snap = reg.snapshot()
    # instruments created AFTER the snapshot must delta from 0
    obs_metrics.inc("testobs.a", 2)
    obs_metrics.inc("testobs.a", 3)
    obs_metrics.set_gauge("testobs.g", 7.5)
    obs_metrics.observe("testobs.h", 1.0)
    obs_metrics.observe("testobs.h", 3.0)
    d = reg.delta(snap)
    assert d["counters"]["testobs.a"] == 5
    assert d["gauges"]["testobs.g"] == 7.5
    assert d["histograms"]["testobs.h"]["count"] == 2
    assert d["histograms"]["testobs.h"]["total"] == 4.0
    h = reg.histogram("testobs.h").summary()
    assert h["mean"] == 2.0 and h["min"] == 1.0 and h["max"] == 3.0
    snap2 = reg.snapshot()
    obs_metrics.inc("testobs.a")
    assert reg.delta(snap2)["counters"]["testobs.a"] == 1


def test_registry_counter_thread_safe():
    reg = obs_metrics.registry()
    snap = reg.snapshot()

    def bump():
        for _ in range(500):
            obs_metrics.inc("testobs.race")

    ts = [threading.Thread(target=bump) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.delta(snap)["counters"]["testobs.race"] == 2000


# ---------------------------------------------------------------------------
# Export: manifests, schema, JSONL
# ---------------------------------------------------------------------------

def test_config_digest_stable_and_order_free():
    a = config_digest({"a": 1, "b": [2, 3]})
    b = config_digest({"b": [2, 3], "a": 1})
    assert a == b and len(a) == 12
    assert config_digest({"a": 2, "b": [2, 3]}) != a


def test_run_manifest_keys():
    m = run_manifest(seed=7, config={"x": 1}, extra={"note": "t"})
    for k in ("git_sha", "python", "jax", "numpy", "platform", "device",
              "argv", "time_unix"):
        assert k in m, k
    assert m["seed"] == 7 and m["note"] == "t"
    dev = jax.devices()[0]
    assert m["device"] == {"platform": dev.platform,
                           "device_kind": dev.device_kind,
                           "count": len(jax.devices())}
    json.dumps(m)                      # artifacts embed it as JSON
    assert len(m["config_digest"]) == 12


def test_chrome_trace_schema_valid():
    obs_trace.enable()
    with obs_trace.span("a", epoch=0):
        with obs_trace.span("b", track="uploader"):
            pass
    obs_trace.event("fault.test", site="x")
    doc = chrome_trace(manifest=run_manifest(seed=1, config={"k": 1}))
    assert validate_chrome_trace(doc) == []
    assert {"main", "uploader"} <= trace_track_names(doc)
    assert trace_span_names(doc) == {"a", "b"}
    assert doc["metadata"]["seed"] == 1
    assert "config_digest" in doc["metadata"]
    instants = {ev["name"] for ev in doc["traceEvents"]
                if ev.get("ph") == "i"}
    assert "fault.test" in instants
    assert doc["otherData"]["span_records"] == 3


def test_validate_catches_defects():
    bad = {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 9,
                            "ts": -1.0, "dur": 1.0}],
           "metadata": {}}
    problems = validate_chrome_trace(bad)
    assert any("bad ts" in p for p in problems)
    assert any("thread_name" in p for p in problems)
    assert validate_chrome_trace({}) == ["traceEvents missing or not a list"]


def test_write_metrics_jsonl_roundtrip(tmp_path):
    p = write_metrics_jsonl(tmp_path / "m.jsonl", [{"a": 1}, {"b": "x"}],
                            manifest={"git_sha": "deadbeef"})
    lines = [json.loads(line) for line in p.read_text().splitlines()]
    assert lines[0] == {"manifest": {"git_sha": "deadbeef"}}
    assert lines[1:] == [{"a": 1}, {"b": "x"}]


def test_bench_json_carries_manifest(tmp_path):
    from benchmarks.common import Bench
    b = Bench("obstest")
    b.emit("case", "metric", 1)
    out = json.loads(b.save_json(path=tmp_path / "B.json",
                                 seed=5).read_text())
    assert out["results"]["case"]["metric"] == 1
    m = out["manifest"]
    assert {"git_sha", "python", "jax", "numpy", "platform"} <= set(m)
    assert m["seed"] == 5


# ---------------------------------------------------------------------------
# Training stack: parity, coverage, publication
# ---------------------------------------------------------------------------

EPOCHS, ITERS, BATCH = 3, 4, 8


@pytest.fixture(scope="module")
def traced_pair(partitioned):
    """One pipelined + cached config run twice — tracing off (reference)
    then on — with the on-run's registry delta, drained records, and
    exported document captured eagerly."""
    d = partitioned
    cfg = _cfg(d)
    kw = dict(cache_policy="lfu", cache_budget_bytes=1 << 20,
              loss_sync_iters=2)
    obs_trace.disable()
    obs_trace.clear()
    tr_off = _trainer(d, cfg, **kw)
    st_off = tr_off.fit(epochs=EPOCHS, iters_per_epoch=ITERS,
                        batch_per_model=BATCH)
    snap = obs_metrics.registry().snapshot()
    obs_trace.enable()
    try:
        tr_on = _trainer(d, cfg, **kw)
        st_on = tr_on.fit(epochs=EPOCHS, iters_per_epoch=ITERS,
                          batch_per_model=BATCH)
    finally:
        obs_trace.disable()
    delta = obs_metrics.registry().delta(snap)
    recs = obs_trace.records()
    doc = chrome_trace(manifest=run_manifest(seed=0))
    obs_trace.clear()
    return dict(tr_off=tr_off, tr_on=tr_on, st_off=st_off, st_on=st_on,
                delta=delta, recs=recs, doc=doc)


def test_tracing_is_bit_neutral(traced_pair):
    """Tracing on must be bit-identical to tracing off: losses exact,
    every parameter leaf bit-equal (tracing only reads clocks)."""
    tp = traced_pair
    assert [s.loss for s in tp["st_on"]] == [s.loss for s in tp["st_off"]]
    for a, b in zip(jax.tree.leaves(tp["tr_off"].params),
                    jax.tree.leaves(tp["tr_on"].params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tracing_overhead_lenient(traced_pair):
    """Loose tier-1 bound on a noisy shared core; the strict 1.05x
    steady-iteration gate runs in benchmarks/obs.py under CI."""
    tp = traced_pair
    off = min(s.steady_time_s for s in tp["st_off"][1:])
    on = min(s.steady_time_s for s in tp["st_on"][1:])
    assert on <= 2.0 * off, (on, off)


def test_trace_covers_four_tracks_and_iteration_spans(traced_pair):
    doc = traced_pair["doc"]
    assert validate_chrome_trace(doc) == []
    assert {"main", "prefetch", "uploader",
            "cache+readahead"} <= trace_track_names(doc)
    assert {"plan.build", "plan.wait", "upload.commit", "dispatch",
            "loss.sync", "cache.refresh",
            "cache.forecast"} <= trace_span_names(doc)


def test_pipelined_spans_nest_sanely(traced_pair):
    recs = traced_pair["recs"]
    assert all(r.depth >= 0 for r in recs)
    builds = [r for r in recs if r.name == "plan.build"]
    assert builds and all(r.track.startswith("prefetch") for r in builds)
    # planner fan-out work nests under plan.build when run inline (1-core
    # container) or lands on its own planner track when a pool exists
    samples = [r for r in recs if r.name == "plan.sample"]
    assert samples
    assert all(r.depth >= 1 or r.track.startswith("plan") for r in samples)
    commits = [r for r in recs if r.name == "upload.commit"]
    assert commits and {r.track for r in commits} == {"uploader"}


PLANNER_STAGES = ("planner.sample", "planner.dedup", "planner.translate",
                  "planner.account")


def test_planner_stage_spans_nest_once_per_plan(traced_pair):
    """Each planner stage is one span per planned iteration, on the
    building thread, directly inside its plan.build (a build that first
    probes the shape budget plans twice)."""
    recs = traced_pair["recs"]
    builds = [r for r in recs if r.name == "plan.build"]
    probes = traced_pair["tr_on"].budget.probes
    assert builds
    for stage in PLANNER_STAGES:
        mine = [r for r in recs if r.name == stage]
        nested = [r for r in mine for b in builds
                  if r.track == b.track and r.depth == b.depth + 1
                  and b.t0_ns <= r.t0_ns and r.t1_ns <= b.t1_ns]
        assert len(nested) == len(mine) == len(builds) + probes, stage


@pytest.mark.parametrize("pregather", [True, False])
def test_planner_stage_spans_once_per_plan_iteration(partitioned, pregather):
    from repro.core import plan_iteration
    d = partitioned
    rng = np.random.default_rng(0)
    roots = [rng.choice(d["ds"].train_vertices(), 8, replace=False)
             for _ in range(d["parts"])]
    obs_trace.enable()
    with obs_trace.span("plan.build"):
        plan_iteration(d["ds"].graph, d["ds"].labels, d["part"], d["owner"],
                       d["local_idx"], d["table"].shape[1], roots,
                       num_layers=2, fanout=4, pregather=pregather,
                       sample_seed=3)
    recs = obs_trace.records()
    stages = [r.name for r in recs if r.name.startswith("planner.")]
    assert stages == list(PLANNER_STAGES)
    assert all(r.depth == 1 for r in recs if r.name in PLANNER_STAGES)


def test_epoch_stats_published_to_registry(traced_pair):
    tp = traced_pair
    d = tp["delta"]
    assert d["histograms"]["epoch.time_s"]["count"] == EPOCHS
    assert obs_metrics.registry().gauge("epoch.loss").value == \
        tp["st_on"][-1].loss
    assert d["counters"]["epoch.remote_rows"] == \
        sum(s.remote_rows for s in tp["st_on"])
    assert d["counters"]["epoch.cache_hit_rows"] == \
        sum(s.cache_hit_rows for s in tp["st_on"])
    # cache mutations land too (installs happen on the worker thread)
    assert d["counters"].get("cache.installs", 0) >= 1


# ---------------------------------------------------------------------------
# Counter-surface unification (TierStats / CommCounters / faults / ckpt)
# ---------------------------------------------------------------------------

def test_tierstats_registry_write_through(partitioned, tmp_path):
    d = partitioned
    store = FeatureStore.build(
        np.asarray(d["ds"].features), d["part"], d["parts"],
        directory=str(tmp_path),
        host_budget_bytes=max(1, int(d["table"].nbytes) // 8))
    reg = obs_metrics.registry()
    snap = reg.snapshot()
    s0 = (store.stats.t1_rows, store.stats.t2_rows, store.stats.gathers,
          store.stats.readahead_rows)
    store.gather(0, np.arange(8))
    store.readahead(0, np.arange(8))
    delta = reg.delta(snap)["counters"]
    s1 = (store.stats.t1_rows, store.stats.t2_rows, store.stats.gathers,
          store.stats.readahead_rows)
    assert delta.get("features.t1_rows", 0) + \
        delta.get("features.t2_rows", 0) == (s1[0] - s0[0]) + (s1[1] - s0[1])
    assert delta["features.gathers"] == s1[2] - s0[2]
    assert delta["features.readahead_rows"] == s1[3] - s0[3] > 0


def test_resilient_call_lands_on_registry_and_trace():
    obs_trace.enable()
    reg = obs_metrics.registry()
    snap = reg.snapshot()
    state = {"calls": 0}

    def flaky():
        state["calls"] += 1
        if state["calls"] == 1:
            raise TransientCommError("injected")
        return 42

    out = resilient_call(flaky, policy=RetryPolicy(backoff_s=0.0001),
                         epoch=1, it=2)
    assert out == 42
    assert reg.delta(snap)["counters"]["comm.retries"] >= 1
    marks = [r for r in obs_trace.records()
             if r.kind == "i" and r.name == "comm.retry"]
    assert marks and marks[0].tags["attempt"] == 0
    assert marks[0].tags["epoch"] == 1 and marks[0].tags["it"] == 2


def test_fault_marks_appear_in_exported_trace(partitioned):
    """A faulted run's timeline must carry the injected-fault instant
    marks (tagged site/epoch/it) and the registry must count firings."""
    d = partitioned
    fp = FaultPlan([FaultSpec("comm_delay", epoch=0, it=1, delay_s=0.002),
                    FaultSpec("comm_drop", epoch=1, it=2, drops=1)])
    reg = obs_metrics.registry()
    snap = reg.snapshot()
    obs_trace.enable()
    try:
        tr = _trainer(d, _cfg(d))
        with fp.active():
            tr.fit(epochs=2, iters_per_epoch=4, batch_per_model=8)
    finally:
        obs_trace.disable()
    assert fp.fired_count() >= 2
    doc = chrome_trace()
    assert validate_chrome_trace(doc) == []
    instants = {ev["name"] for ev in doc["traceEvents"]
                if ev.get("ph") == "i"}
    assert {"fault.comm_delay", "fault.comm_drop"} <= instants
    delta = reg.delta(snap)["counters"]
    assert delta["faults.fired"] >= 2
    assert delta["faults.comm_delay"] >= 1
    assert delta["faults.comm_drop"] >= 1
    # the dropped exchange was retried, and the retry is on the registry
    assert delta["comm.retries"] >= 1


def test_readahead_spans_on_streamed_store(partitioned, tmp_path):
    d = partitioned
    store = FeatureStore.build(
        np.asarray(d["ds"].features), d["part"], d["parts"],
        directory=str(tmp_path),
        host_budget_bytes=max(1, int(d["table"].nbytes) // 4))
    obs_trace.enable()
    try:
        tr = _trainer(d, _cfg(d), table=store)
        tr.fit(epochs=2, iters_per_epoch=3, batch_per_model=8)
    finally:
        obs_trace.disable()
    names = {r.name for r in obs_trace.records() if r.kind == "X"}
    assert {"features.readahead", "features.readahead.forecast"} <= names


def test_checkpoint_spans_and_counters(tmp_path):
    tree = {"w": np.arange(4.0, dtype=np.float32),
            "b": np.zeros(2, np.float32)}
    obs_trace.enable()
    snap = obs_metrics.registry().snapshot()
    save_checkpoint(tmp_path, 3, tree)
    restored, step, _ = load_checkpoint(tmp_path, tree)
    obs_trace.disable()
    names = {r.name for r in obs_trace.records() if r.kind == "X"}
    assert {"ckpt.save", "ckpt.load"} <= names
    delta = obs_metrics.registry().delta(snap)["counters"]
    assert delta["ckpt.saves"] == 1 and delta["ckpt.loads"] == 1
    assert step == 3
    np.testing.assert_array_equal(restored["w"], tree["w"])
