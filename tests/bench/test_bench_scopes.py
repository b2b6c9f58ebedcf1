"""Device time by program scope and the program's spans on the profiler's
clock: the scope matcher against the metadata JAX writes for the fused
step, the reduction on hand-made records, the program's spans in a CPU
profile, and a tiny fused step recorded on a v5e (kept in
bench/testdata)."""
import functools
import re
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cell as cells  # noqa: E402
from bench import scopes  # noqa: E402
from bench import tracereduce as tr  # noqa: E402

V5E = ROOT / "bench" / "testdata"
# the recorded step's annotation, entered just before its one step
V5E_ANCHOR = "tiny.anchor"
DEVICE_METRICS = ("gather.relayout_ms_per_iter", "gather.kernel_ms_per_iter",
                  "exchange.device_ms_per_iter", "step.compute_ms_per_iter",
                  "device.unscoped_share")
STAGES = ("sample", "dedup", "translate", "account")


@pytest.mark.parametrize("op_name, path", [
    ("jit(step)/while/body/closed_call/transpose(jvp(layers))/dot_general",
     "layers"),
    ("jit(step)/while/body/closed_call/jvp(gather)/cond/branch_0_fun/"
     "kernel/pallas_call", "gather/kernel"),
    ("jit(step)/while/body/closed_call/jvp(gather)/jit(_take)/gather",
     "gather"),
    ("jit(step)/exchange/gather", "exchange"),
    ("jit(step)/exchange/exchange/concatenate", "exchange"),
    ("jit(step)/update/add", "update"),
    ("jit(step)/while/body/closed_call", None),
    ("jit(step)/gather", None),
    ("", None),
    (None, None),
])
def test_bench_scope_path(op_name, path):
    assert scopes.scope_path(op_name) == path


@pytest.fixture(scope="module", params=[True, False],
                ids=["pregather", "per-step"])
def fused_step_op_names(partitioned, request):
    """op_name of every instruction of the engine's fused step compiled at
    a tiny size, the gather kernel in interpret mode."""
    import jax
    from repro.core import distributed as engine
    from repro.core import plan_iteration
    from repro.kernels import ops
    from repro.models.gnn import GNNConfig, init_gnn
    from repro.optim import adam
    d = partitioned
    rng = np.random.default_rng(0)
    tv = d["ds"].train_vertices()
    roots = [rng.choice(tv, 12, replace=False) for _ in range(d["parts"])]
    plan = plan_iteration(d["ds"].graph, d["ds"].labels, d["part"],
                          d["owner"], d["local_idx"], d["table"].shape[1],
                          roots, num_layers=2, fanout=4, sample_seed=7,
                          pregather=request.param)
    cfg = GNNConfig(model="sage", num_layers=2, hidden_dim=16,
                    feature_dim=d["ds"].feature_dim,
                    num_classes=d["ds"].num_classes, fanout=4)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    opt = adam(1e-3, key=("scopes-test",))
    fn = engine._build_fused(cfg, request.param, False, None, "data", opt,
                             False)
    args = engine.prepare_iteration_args(d["table"], plan)
    real = ops.gather_rows
    ops.gather_rows = functools.partial(real, force_kernel=True)
    try:
        text = fn.lower(params, opt.init(params), *args).compile().as_text()
    finally:
        ops.gather_rows = real
    return re.findall(r'op_name="([^"]*)"', text)


def test_bench_scopes_of_the_compiled_fused_step(fused_step_op_names):
    paths = {scopes.scope_path(n) for n in fused_step_op_names}
    assert {"exchange", "gather", "gather/kernel", "layers",
            "update"} <= paths
    assert paths <= {None, "exchange", "gather", "gather/kernel", "layers",
                     "update"}
    # the interpret-mode kernel's ops all fall under gather/kernel
    assert all(scopes.scope_path(n) == "gather/kernel"
               for n in fused_step_op_names if "/kernel/" in n)


def test_bench_backward_ops_map_to_layers(fused_step_op_names):
    backward = [n for n in fused_step_op_names if "transpose(" in n]
    assert backward
    assert {scopes.scope_path(n) for n in backward} == {"layers"}


HAND = {
    "window_ns": [0, 100],
    "devices": [
        [["%while.1 = (s32[]) while(s32[] %p)", 0, 100],
         ["%kernel.3 = f32[8,1,128]{2,1,0} custom-call(s32[8]{0} %a)", 5, 20],
         ["%pad.2 = f32[8,128]{1,0} pad(f32[8,100]{1,0} %t)", 25, 10],
         ["%fusion.4 = f32[8]{0} fusion(f32[8]{0} %x)", 40, 30],
         ["%copy.5 = f32[8]{0} copy(f32[8]{0} %y)", 90, 20]],
        [["%fusion.4 = f32[8]{0} fusion(f32[8]{0} %x)", -10, 30]]],
    "device_scopes": [[None, "gather/kernel", "gather", "layers", None],
                      ["exchange"]],
    "host": [["plan.build", "prefetch_0", 0, 80],
             ["planner.sample", "prefetch_0", 5, 30],
             ["planner.dedup", "prefetch_0", 40, 20],
             ["plan.build", "prefetch_0", 80, 40],
             ["planner.sample", "prefetch_0", 85, 10]],
}


def test_bench_scope_ns_clips_and_leaves_out_containers():
    assert scopes.scope_ns(HAND, "gather") == [30, 0]
    assert scopes.scope_ns(HAND, "gather", exclude="gather/kernel") == [10, 0]
    assert scopes.scope_ns(HAND, "gather/kernel") == [20, 0]
    assert scopes.scope_ns(HAND, "exchange") == [0, 20]      # [0, 20)
    assert scopes.scope_ns(HAND, "layers") == [30, 0]
    # the copy runs [90, 110): 10 in the window; the while is a container
    assert scopes.unscoped_ns(HAND) == (10, 20 + 10 + 30 + 10 + 20)
    rec = dict(HAND, window_ns=[30, 60])
    assert scopes.scope_ns(rec, "gather") == [5, 0]
    assert scopes.scope_ns(rec, "layers") == [20, 0]


def test_bench_device_readers():
    run = SimpleNamespace(record=HAND, window={"iters": 2})
    got = {m: cells.load_metric(m).read(run) for m in DEVICE_METRICS}
    assert got == pytest.approx({
        "gather.relayout_ms_per_iter": 10 / 2 / 2 / 1e6,
        "gather.kernel_ms_per_iter": 20 / 2 / 2 / 1e6,
        "exchange.device_ms_per_iter": 20 / 2 / 2 / 1e6,
        "step.compute_ms_per_iter": 30 / 2 / 2 / 1e6,
        "device.unscoped_share": 100 * 10 / 90})


def test_bench_readers_read_nothing_from_a_program_without_scopes():
    rec = {k: v for k, v in HAND.items() if k != "device_scopes"}
    rec["host"] = [s for s in HAND["host"] if s[0] == "plan.build"]
    run = SimpleNamespace(record=rec, window={"iters": 2})
    for m in DEVICE_METRICS + tuple(f"planner.{s}_ms_per_iter"
                                    for s in STAGES):
        assert cells.load_metric(m).read(run) is None, m


def test_bench_planner_stage_readers():
    run = SimpleNamespace(record=HAND, window={"iters": 2})
    read = {s: cells.load_metric(f"planner.{s}_ms_per_iter").read(run)
            for s in STAGES}
    assert read["sample"] == pytest.approx(40 / 2 / 1e6)
    assert read["dedup"] == pytest.approx(20 / 2 / 1e6)
    assert read["translate"] is None and read["account"] is None


@pytest.fixture()
def obs_on():
    from repro.obs import trace as obs
    obs.enable()
    yield obs
    obs.disable()
    obs.clear()


def test_bench_planner_offcpu_share(obs_on):
    t_open = time.perf_counter()
    with obs_on.span("planner.dedup"):
        time.sleep(0.05)                       # off the CPU
    with obs_on.span("plan.sample"):
        t = time.thread_time()
        while time.thread_time() - t < 0.05:   # on it
            pass
    with obs_on.span("plan.wait"):             # not a planner leaf
        time.sleep(0.05)
    run = SimpleNamespace(window={"t_open": t_open,
                                  "t_close": time.perf_counter()})
    share = cells.load_metric("planner.offcpu_share").read(run)
    leaves = [r for r in obs_on.records() if r.name != "plan.wait"]
    wall = sum(r.dur_ns for r in leaves)
    assert share == pytest.approx(
        100 * (1 - sum(r.cpu_ns for r in leaves) / wall))
    # the sleep alone keeps the leaves off the CPU for over 40 ms
    assert 100 * 0.04e9 / wall < share < 100


def test_bench_program_spans_on_the_profiler_clock(obs_on, tmp_path):
    """Spans of the program, on two threads, appear in the profile with
    their names and nesting, where the anchor mapping puts them."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.anchor"):
            anchor_pc = time.perf_counter_ns()
        with obs_on.span("outer"):
            with obs_on.span("inner"):
                time.sleep(0.002)
            th = threading.Thread(target=lambda: obs_on.span("pool")
                                  .__enter__().__exit__(None, None, None),
                                  name="plan_0")
            th.start()
            th.join()
    finally:
        jax.profiler.stop_trace()
    recs = {r.name: r for r in obs_on.records()}
    got = scopes.from_profile(str(tmp_path),
                              {"test.anchor", "outer", "inner", "pool"})
    assert got["device_scopes"] == []
    native = {s[0]: s for s in got["host_native"]}
    assert set(native) == {"test.anchor", "outer", "inner", "pool"}
    off = native["test.anchor"][2] - anchor_pc
    for name in ("outer", "inner", "pool"):
        assert abs(native[name][2] - (recs[name].t0_ns + off)) < 0.2e6, name
    (_, t_o, s_o, d_o), (_, t_i, s_i, d_i) = (native["outer"][:4],
                                              native["inner"][:4])
    assert t_o == t_i and s_o <= s_i and s_i + d_i <= s_o + d_o


@pytest.fixture(scope="module")
def v5e_record():
    """record_from_profile of the recorded v5e step, with its scopes."""
    # the whole trace: its device ops stamp about 0.7 ms before the host
    # annotation that precedes their dispatch
    rec = tr.record_from_profile(str(V5E), V5E_ANCHOR, 0, [],
                                 (-10 ** 15, 10 ** 15))
    rec.update(scopes.from_profile(str(V5E), set()))
    return rec


def test_bench_v5e_trace_recovers_the_four_scopes(v5e_record):
    rec = v5e_record
    assert [len(p) for p in rec["device_scopes"]] == \
        [len(ev) for ev in rec["devices"]]
    paths = {p for ps in rec["device_scopes"] for p in ps}
    assert {"exchange", "gather", "gather/kernel", "layers",
            "update"} <= paths
    none, every = scopes.unscoped_ns(rec)
    assert 0 < none < 0.15 * every


def test_bench_v5e_kernel_scope_matches_its_hlo_pattern(v5e_record):
    accept = cells.load_metric("gather_rows_roofline").ACCEPT
    assert scopes.scope_ns(v5e_record, "gather/kernel") == \
        tr.op_ns(v5e_record, accept)
    run = SimpleNamespace(record=v5e_record, window={"iters": 1})
    for m in DEVICE_METRICS:
        assert cells.load_metric(m).read(run) > 0, m
