"""repro.resilience: fault injection, supervised recovery, degradation.

The contract under test everywhere here is *bit-identity*: the stack's
determinism (plans are pure functions of (epoch, it, seeds, pattern,
cache_version); every pipeline/cache/tier mode is bit-identical to its
fallback) means an absorbed fault must leave zero numerical trace. Each
test injects a fault class, asserts it actually fired, and asserts the
run's losses/parameters equal the fault-free run's exactly.
"""
import json
import subprocess
import sys
import textwrap
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.features import CorruptFeatureError, FeatureStore
from repro.models.gnn import GNNConfig
from repro.optim import adam
from repro.resilience import (BackgroundError, ChaosPlan,
                              CheckpointRollbackExhausted, CommCounters,
                              CommTimeout, FaultPlan, FaultSpec,
                              ResiliencePolicy, RetryPolicy,
                              ThreadSupervisor, TransientCommError,
                              resilient_call)
from repro.train import Trainer

ROOT = Path(__file__).resolve().parents[1]


def _cfg(d):
    return GNNConfig(model="sage", num_layers=2, hidden_dim=16,
                     feature_dim=d["ds"].feature_dim,
                     num_classes=d["ds"].num_classes, fanout=4)


def _trainer(d, cfg, **kw):
    kw.setdefault("optimizer", adam(5e-3))
    kw.setdefault("merging", False)
    kw.setdefault("train_vertices", d["ds"].train_vertices())
    return Trainer(graph=d["ds"].graph, labels=d["ds"].labels,
                   part=d["part"], owner=d["owner"],
                   local_idx=d["local_idx"], table=d["table"], cfg=cfg, **kw)


def _losses(stats):
    return [s.loss for s in stats]


def _run(d, plan=None, epochs=2, iters=4, **kw):
    tr = _trainer(d, _cfg(d), **kw)
    if plan is not None:
        with plan.active():
            stats = tr.fit(epochs=epochs, iters_per_epoch=iters,
                           batch_per_model=8)
    else:
        stats = tr.fit(epochs=epochs, iters_per_epoch=iters,
                       batch_per_model=8)
    return tr, stats


def _assert_params_equal(a, b):
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture(scope="module")
def clean(partitioned):
    """Fault-free baseline for the default (resident, pipelined) config."""
    tr, stats = _run(partitioned)
    return tr, _losses(stats)


# ---------------------------------------------------------------------------
# Chaos parity, one fault class at a time
# ---------------------------------------------------------------------------

def test_comm_delay_is_absorbed(partitioned, clean):
    fp = FaultPlan([FaultSpec("comm_delay", epoch=0, it=1, delay_s=0.002),
                    FaultSpec("comm_delay", epoch=1, it=2, delay_s=0.002)])
    tr, stats = _run(partitioned, fp)
    assert fp.fired_count() == 2
    assert _losses(stats) == clean[1]
    assert all(s.epoch_attempts == 1 for s in stats)   # pure wall-clock
    _assert_params_equal(tr, clean[0])


def test_comm_drop_is_retried(partitioned, clean):
    fp = FaultPlan([FaultSpec("comm_drop", epoch=0, it=2, drops=2,
                              once=False)])
    _, stats = _run(partitioned, fp)
    assert _losses(stats) == clean[1]
    assert stats[0].comm_retries >= 2
    assert stats[0].comm_timeouts == 0


def test_prefetch_thread_death_replays_bit_identical(partitioned, clean):
    fp = FaultPlan([FaultSpec("thread_exc", epoch=1, it=1,
                              site="prefetch")])
    tr, stats = _run(partitioned, fp)
    assert fp.fired_count() == 1
    assert _losses(stats) == clean[1]
    assert stats[1].bg_errors >= 1
    assert stats[1].epoch_attempts == 2        # one in-mode replay
    assert tr.pipeline                          # no degradation needed
    _assert_params_equal(tr, clean[0])


def test_nan_loss_rolls_back_and_replays(partitioned, clean):
    fp = FaultPlan([FaultSpec("nan_loss", epoch=1, it=1)])
    tr, stats = _run(partitioned, fp)
    assert _losses(stats) == clean[1]
    assert stats[1].rollbacks == 1
    assert np.isfinite(stats[1].loss)
    _assert_params_equal(tr, clean[0])


def test_nan_divergence_exhausts_rollbacks(partitioned):
    # a NaN that re-fires on every replay is genuine divergence
    fp = FaultPlan([FaultSpec("nan_loss", epoch=0, it=0, once=False)])
    with pytest.raises(CheckpointRollbackExhausted):
        _run(partitioned, fp)


def test_stalled_prefetch_hits_deadline_and_recovers(partitioned, clean):
    policy = ResiliencePolicy(stall_deadline_s=0.25)
    fp = FaultPlan([FaultSpec("thread_stall", epoch=0, it=0,
                              site="prefetch", delay_s=1.0)])
    _, stats = _run(partitioned, fp, resilience=policy)
    assert _losses(stats) == clean[1]
    assert stats[0].epoch_attempts >= 2


def test_persistent_comm_drop_raises_comm_timeout(partitioned):
    fp = FaultPlan([FaultSpec("comm_drop", epoch=0, it=1, drops=99,
                              once=False)])
    with pytest.raises(CommTimeout):
        _run(partitioned, fp,
             resilience=ResiliencePolicy(
                 retry=RetryPolicy(max_retries=2, backoff_s=0.001)))


# ---------------------------------------------------------------------------
# Degradation ladder
# ---------------------------------------------------------------------------

def test_persistent_prefetch_fault_degrades_to_sync(partitioned, clean):
    """A prefetch thread that dies on every submission must walk the
    ladder: replay in-mode, then pipeline→sync with inline planning —
    which stops tripping the fault (inline planning is not the 'prefetch'
    site) and stays bit-identical by the pipeline≡sync gate."""
    fp = FaultPlan([FaultSpec("thread_exc", epoch=0, it=-1,
                              site="prefetch", once=False)])
    tr, stats = _run(partitioned, fp)
    assert _losses(stats) == clean[1]
    assert "pipeline_to_sync" in tr.degradations_taken
    assert not tr.pipeline
    assert stats[0].epoch_attempts >= 3
    _assert_params_equal(tr, clean[0])


def test_persistent_cache_fault_degrades_to_cache_off(partitioned):
    # the epoch-(e+1) cache compute runs during epoch e, so where the
    # failure surfaces depends on thread timing; two failing epochs make
    # the second site failure — and the cache_off rung — certain
    ctr, cs = _run(partitioned, epochs=4, cache_policy="lfu",
                   cache_budget_bytes=1 << 18)
    fp = FaultPlan([FaultSpec("thread_exc", epoch=1, site="cache",
                              once=False),
                    FaultSpec("thread_exc", epoch=2, site="cache",
                              once=False)])
    tr, stats = _run(partitioned, fp, epochs=4, cache_policy="lfu",
                     cache_budget_bytes=1 << 18)
    assert _losses(stats) == _losses(cs)       # cache parity gate
    assert "cache_off" in tr.degradations_taken
    assert tr.cache_store is None
    _assert_params_equal(tr, ctr)


def test_persistent_readahead_fault_degrades_to_resident_gather(
        partitioned):
    d = partitioned

    def tiered():
        return FeatureStore.build(d["ds"].features, d["part"], d["parts"],
                                  host_budget_bytes=1 << 20)
    ctr, cs = _run(dict(d, table=tiered()), epochs=4)
    fp = FaultPlan([FaultSpec("thread_exc", epoch=1, site="readahead",
                              once=False),
                    FaultSpec("thread_exc", epoch=2, site="readahead",
                              once=False)])
    tr, stats = _run(dict(d, table=tiered()), fp, epochs=4)
    assert _losses(stats) == _losses(cs)       # tier parity gate
    assert "resident_gather" in tr.degradations_taken
    assert tr.store.hot_bypass and not tr._readahead_enabled
    _assert_params_equal(tr, ctr)


def test_device_error_in_uploader_is_raised_not_degraded(partitioned,
                                                         monkeypatch):
    """A device runtime error (here a simulated HBM exhaustion) in the
    uploader thread's transfer is raised from fit: no replay and no ladder
    rung hides it behind a slower mode."""
    from repro.train.pipeline import PlanUploader

    def oom(self, plan):
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: out of memory allocating plan buffers")
    monkeypatch.setattr(PlanUploader, "commit", oom)
    tr = _trainer(partitioned, _cfg(partitioned))
    with pytest.raises(BackgroundError) as info:
        tr.fit(epochs=2, iters_per_epoch=4, batch_per_model=8)
    assert isinstance(info.value.__cause__, jax.errors.JaxRuntimeError)
    assert tr.degradations_taken == []
    assert tr.pipeline


# ---------------------------------------------------------------------------
# The headline gate: mixed recoverable plan, streamed store, bit-identical
# ---------------------------------------------------------------------------

def test_recoverable_faultplan_headline_gate(partitioned, tmp_path):
    """Thread kill + straggler + dropped exchange + corrupted disk rows +
    a NaN step, all in one run over the full streamed stack — training
    completes with losses AND parameters bit-identical to fault-free."""
    d = partitioned

    def run(plan, directory):
        store = FeatureStore.build(
            d["ds"].features, d["part"], d["parts"], directory=directory,
            host_budget_bytes=1 << 20, crc_chunk_rows=64)
        return _run(dict(d, table=store), plan, epochs=3, iters=6)

    tr1, cs = run(None, str(tmp_path / "clean"))
    fp = FaultPlan.recoverable(seed=3)
    tr2, fs = run(fp, str(tmp_path / "faulty"))

    kinds = {k for (k, *_rest) in fp.fired}
    assert kinds == {"thread_exc", "comm_delay", "comm_drop",
                     "disk_corrupt", "nan_loss"}
    assert _losses(fs) == _losses(cs)
    _assert_params_equal(tr1, tr2)
    assert tr2.store.stats.crc_failures >= 1       # corruption was caught
    assert tr2.store.stats.repaired_rows >= 1      # ...and repaired
    assert sum(s.rollbacks for s in fs) == 1


# ---------------------------------------------------------------------------
# ChaosPlan
# ---------------------------------------------------------------------------

def test_chaos_plan_is_deterministic_and_transient_only():
    a, b = ChaosPlan(seed=11, rate=0.5), ChaosPlan(seed=11, rate=0.5)
    for e in range(4):
        for i in range(16):
            sa = a._take("comm_delay", e, i)
            sb = b._take("comm_delay", e, i)
            assert [s.delay_s for s in sa] == [s.delay_s for s in sb]
    assert a._take("disk_corrupt", 0, 0) == []     # never destructive
    assert a._take("nan_loss", 0, 0) == []
    assert a._take("thread_exc", 0, 0) == []


def test_training_under_chaos_is_bit_identical(partitioned, clean):
    tr, stats = _run(partitioned, ChaosPlan(seed=5, rate=0.5,
                                            max_delay_s=0.001))
    assert _losses(stats) == clean[1]
    _assert_params_equal(tr, clean[0])


# ---------------------------------------------------------------------------
# Supervisor + retry wrapper units
# ---------------------------------------------------------------------------

def test_supervisor_surfaces_background_error_with_context():
    sup = ThreadSupervisor()
    pool = ThreadPoolExecutor(max_workers=1)
    try:
        def boom():
            raise RuntimeError("dead worker")
        fut = sup.submit(pool.submit, "prefetch", boom, epoch=3, it=7)
        with pytest.raises(BackgroundError) as ei:
            fut.result(timeout=5)
        assert ei.value.site == "prefetch"
        assert (ei.value.epoch, ei.value.it) == (3, 7)
        sup.mark_delivered(ei.value)
        sup.check()                     # delivered once — no double raise
        # an undelivered error raises at the next boundary check
        sup.submit(pool.submit, "cache", boom, epoch=4, it=-1).exception(
            timeout=5)
        with pytest.raises(BackgroundError) as ei2:
            sup.check()
        assert ei2.value.site == "cache" and ei2.value.epoch == 4
        assert sup.drain() == []
    finally:
        pool.shutdown(wait=False)


def test_resilient_call_retries_then_times_out():
    counters = CommCounters()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise TransientCommError("drop")
        return "ok"
    out = resilient_call(flaky, policy=RetryPolicy(backoff_s=0.0001),
                         counters=counters)
    assert out == "ok" and counters.retries == 2 and counters.timeouts == 0

    def dead():
        raise TransientCommError("drop")
    with pytest.raises(CommTimeout) as ei:
        resilient_call(dead, policy=RetryPolicy(max_retries=2,
                                                backoff_s=0.0001),
                       counters=counters, epoch=1, it=2)
    assert counters.timeouts == 1
    assert (ei.value.epoch, ei.value.it) == (1, 2)


# ---------------------------------------------------------------------------
# FeatureStore integrity (crc32 / quarantine / repair)
# ---------------------------------------------------------------------------

@pytest.fixture()
def crc_store(partitioned, tmp_path):
    d = partitioned
    st = FeatureStore.build(d["ds"].features, d["part"], d["parts"],
                            directory=str(tmp_path / "shards"),
                            host_budget_bytes=1 << 20, crc_chunk_rows=64)
    return d, st


def test_disk_corruption_detected_and_repaired(crc_store):
    d, st = crc_store
    rows = np.arange(128, dtype=np.int64)       # covers chunks 0 and 1
    ref = st.gather(0, rows).copy()
    st.corrupt_rows(0, np.array([3, 64, 65]), seed=9)
    out = st.gather(0, rows)
    np.testing.assert_array_equal(ref, out)
    assert st.stats.crc_failures >= 2           # both chunks tripped
    assert st.stats.repaired_rows >= 1


def test_corruption_without_source_refuses_to_serve(crc_store):
    d, st = crc_store
    st._source = None
    st.corrupt_rows(1, np.array([5]))
    with pytest.raises(CorruptFeatureError):
        st.gather(1, np.array([5]))


def test_checksum_sidecars_reload_without_rescan(partitioned, tmp_path):
    d = partitioned
    shards = str(tmp_path / "s")
    st = FeatureStore.build(d["ds"].features, d["part"], d["parts"],
                            directory=shards, host_budget_bytes=1 << 20,
                            crc_chunk_rows=64)
    st2 = FeatureStore(st._backing, host_budget_bytes=1 << 20,
                       owner=d["owner"], local_idx=d["local_idx"])
    st2.crc_chunk_rows = 64
    assert st2._load_sidecars(shards)
    np.testing.assert_array_equal(st._crc[0], st2._crc[0])


def test_mark_suspect_forces_reverification(crc_store):
    d, st = crc_store
    st.gather(0, np.arange(32))
    checked = st.stats.crc_checked_chunks
    st.gather(0, np.arange(32))
    assert st.stats.crc_checked_chunks == checked      # memoized
    st.mark_suspect(0, np.arange(32))
    st.gather(0, np.arange(32))
    assert st.stats.crc_checked_chunks == checked + 1  # re-verified


def test_verify_all_scrubs_and_repairs(crc_store):
    d, st = crc_store
    st.corrupt_rows(2, np.array([10]))
    assert st.verify_all() >= 1
    assert st.verify_all() == 0                        # now clean


# ---------------------------------------------------------------------------
# Crash-atomic checkpoints
# ---------------------------------------------------------------------------

def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (4, 3)), "b": np.zeros(3, np.float32)}


def test_checkpoint_truncated_newest_falls_back(tmp_path):
    from repro.checkpoint import load_checkpoint, save_checkpoint
    t = _tree()
    save_checkpoint(tmp_path, 1, t)
    save_checkpoint(tmp_path, 2, jax.tree.map(lambda x: x + 1, t))
    npz = tmp_path / "step-00000002.npz"
    npz.write_bytes(npz.read_bytes()[:40])             # torn write
    with pytest.warns(RuntimeWarning, match="corrupt"):
        tree, step, _ = load_checkpoint(tmp_path, t)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(tree["w"]),
                                  np.asarray(t["w"]))


def test_checkpoint_explicit_step_fails_loudly(tmp_path):
    from repro.checkpoint import (CheckpointCorrupt, load_checkpoint,
                                  save_checkpoint)
    t = _tree()
    save_checkpoint(tmp_path, 5, t)
    (tmp_path / "step-00000005.json").write_text("{ not json")
    with pytest.raises(CheckpointCorrupt):
        load_checkpoint(tmp_path, t, step=5)


def test_checkpoint_missing_manifest_is_incomplete(tmp_path):
    from repro.checkpoint import (load_checkpoint, save_checkpoint,
                                  valid_steps)
    t = _tree()
    save_checkpoint(tmp_path, 1, t)
    save_checkpoint(tmp_path, 2, t)
    (tmp_path / "step-00000002.json").unlink()         # crash between files
    assert valid_steps(tmp_path) == [1]
    with pytest.warns(RuntimeWarning, match="incomplete"):
        _, step, _ = load_checkpoint(tmp_path, t)
    assert step == 1


def test_checkpoint_leaf_mismatch_still_valueerror(tmp_path):
    # the Trainer's bare-params legacy fallback depends on this contract
    from repro.checkpoint import load_checkpoint, save_checkpoint
    save_checkpoint(tmp_path, 1, _tree())
    with pytest.raises(ValueError, match="leaf count"):
        load_checkpoint(tmp_path, {"w": np.zeros((4, 3))})


def test_checkpoint_leaves_no_temp_files(tmp_path):
    from repro.checkpoint import save_checkpoint
    save_checkpoint(tmp_path, 3, _tree())
    assert not list(tmp_path.glob("*.tmp"))


# ---------------------------------------------------------------------------
# SIGKILL mid-epoch + resume (subprocess)
# ---------------------------------------------------------------------------

def _run_py(code: str, expect_signal=None) -> dict:
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True,
        env={"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        timeout=600)
    if expect_signal is not None:
        assert out.returncode == -expect_signal, out.stderr[-2000:]
        return {}
    assert out.returncode == 0, out.stderr[-3000:]
    for line in out.stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    raise AssertionError(f"no RESULT line in:\n{out.stdout}\n{out.stderr}")


_SIGKILL_PRELUDE = """
import json, os, signal
import numpy as np
import jax
from repro.graph import make_dataset, ldg_partition
from repro.graph.partition import shard_features
from repro.models.gnn import GNNConfig
from repro.optim import adam
from repro.train import Trainer

ds = make_dataset("arxiv", scale=0.02, seed=0)
part = ldg_partition(ds.graph, 4, passes=1)
table, owner, local_idx = shard_features(ds.features, part, 4)
cfg = GNNConfig(model="sage", num_layers=2, hidden_dim=8,
                feature_dim=ds.feature_dim, num_classes=ds.num_classes,
                fanout=4)

def trainer(ckpt):
    return Trainer(graph=ds.graph, labels=ds.labels, part=part,
                   owner=owner, local_idx=local_idx, table=table, cfg=cfg,
                   optimizer=adam(5e-3), merging=False, root_seed=5,
                   train_vertices=ds.train_vertices(), ckpt_dir=ckpt)
"""


def test_sigkill_mid_epoch_resume_is_bit_identical(tmp_path):
    """Kill -9 the training process in the middle of epoch 2 (after epoch
    1's checkpoint is durable), resume from disk, and require the final
    parameters to match an uninterrupted run byte for byte."""
    ck = str(tmp_path / "ck")
    # phase 1: train, SIGKILL the interpreter mid-epoch-2
    _run_py(_SIGKILL_PRELUDE + f"""
tr = trainer({ck!r})
orig = tr.build_plan
def killing(epoch, it, batch):
    if (epoch, it) == (2, 1):
        os.kill(os.getpid(), signal.SIGKILL)
    return orig(epoch, it, batch)
tr.build_plan = killing
tr.fit(epochs=3, iters_per_epoch=3, batch_per_model=8)
""", expect_signal=9)
    # phase 2: resume and finish; phase 3 (same process): straight run
    res = _run_py(_SIGKILL_PRELUDE + f"""
tr = trainer({ck!r})
stats = tr.fit(epochs=3, iters_per_epoch=3, batch_per_model=8,
               resume=True)
tr2 = trainer(None)
tr2.fit(epochs=3, iters_per_epoch=3, batch_per_model=8)
same = all(np.array_equal(np.asarray(a), np.asarray(b))
           for a, b in zip(jax.tree.leaves(tr.params),
                           jax.tree.leaves(tr2.params)))
print("RESULT:" + json.dumps({{
    "resumed_epochs": [s.epoch for s in stats], "identical": same}}))
""")
    assert res["resumed_epochs"] == [2]
    assert res["identical"]
