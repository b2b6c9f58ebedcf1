#!/usr/bin/env python3
"""LeapGNN training benchmark on the TPU: one run of one cell.

    python bench/run.py --workload sage-products.train --seed 7 \\
        --seconds 30 --trace 0

Drives ``Trainer.fit`` on its normal path (``strategy="hopgnn"``,
pre-gathering, the pipelined fused step, the Pallas gather on the TPU):
four workers emulated on one chip, or one per chip over a four-chip mesh.
Each call to ``fit`` trains one epoch whose number no other call uses.

Set-up builds the configuration's graph and table, the initial weights
from ``--seed``, and the Trainer with its merge pattern frozen at the
traffic's ``merge_steps``. It trains the first three iterations (one
epoch of one, one of two: the first gradient is read from the optimizer's
state after one step), then a warm-up epoch whose steady time per
iteration sizes the window: one epoch of as many iterations as fill
``--seconds``, opened and closed on synced devices.

``--trace 0`` reports the end-to-end metrics, ``--trace 1`` the per-layer
ones from the program's spans and counters and a profiler trace of a
shorter window. Either way, once the window has closed and the program's
state is freed, the plain reference trains the first three iterations
from the same initial weights and the check compares them
(``reference.compare``); it also counts the window's iterations whose
roots the program never asked for (a plan it did not build for them).
The last line of stdout is
one JSON object. Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result; so does a run in which
the program did not train as the cell states (``HarnessError``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from bench import cell as cells  # noqa: E402
from bench import graphgen, reference, tracereduce  # noqa: E402
from bench.features import init_params, make_table  # noqa: E402
from bench.traffic import Traffic  # noqa: E402

TRACE_DIR = graphgen.DATA_DIR / "trace"
ANCHOR = "bench.trace_window"
MAIN_TRACK = "MainThread"


class NoChip(RuntimeError):
    pass


class CompileClock:
    """Counts of XLA backend compiles and persistent-cache loads in this
    process, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
            if event.startswith("/jax/core/compile/"):
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple:
        return self.compiles, self.cache_hits


def check_devices(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform} "
                     f"({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def build_data(cfg: dict, say):
    """The configuration's graph, partition, and host feature table."""
    t0 = time.perf_counter()
    graph = graphgen.load_or_generate(cfg["graph"], say)
    t1 = time.perf_counter()
    workers = int(cfg["workers"])
    part = (graph.communities % workers).astype(np.int32)
    owner, local_idx, rows = graphgen.shard_maps(part, workers)
    m = cfg["model"]
    table = np.asarray(make_table(cfg["graph"]["data_seed"], graph.labels,
                                  owner, local_idx, workers, rows,
                                  int(m["feature_dim"]),
                                  int(cfg["graph"]["classes"])))
    say(f"graph {graph.num_vertices} vertices, {graph.num_edges} directed "
        f"edges, {graph.train_mask.sum()} training vertices ({t1 - t0:.3f} s)"
        f"; table {table.shape} float32, {table.nbytes / 1e9:.3f} GB "
        f"({time.perf_counter() - t1:.3f} s)")
    return SimpleNamespace(graph=graph, part=part, owner=owner,
                           local_idx=local_idx, rows=rows, table=table)


def gnn_config(cfg: dict):
    from repro.models.gnn import GNNConfig
    m = cfg["model"]
    return GNNConfig(model=m["layer"], num_layers=int(m["num_layers"]),
                     hidden_dim=int(m["hidden_dim"]),
                     feature_dim=int(m["feature_dim"]),
                     num_classes=int(m["classes"]), fanout=int(m["fanout"]))


class HarnessError(RuntimeError):
    """The program did not train as the cell states: the merge pattern
    was not the traffic's, or ``fit`` did not train the epoch it was
    given. The run then ends without a result."""


# The Trainer samples iteration ``it`` of epoch ``e`` with the seed
# ``sample_seed_base + e * SEED_STRIDE + it``.
SEED_STRIDE = 10_000


class Training:
    """The Trainer under test. Every call to :meth:`epoch` trains one
    Trainer epoch with an epoch number of its own, so no (epoch,
    iteration) pair repeats in a run; its roots and sampling seeds are
    the traffic's, by global iteration."""

    def __init__(self, cfg: dict, tp: dict, data, traffic: Traffic,
                 params0, mesh):
        from repro.graph.structs import CSRGraph
        from repro.optim import adamw
        from repro.train import Trainer
        o = cfg["optimizer"]
        self.traffic = traffic
        self.merge_steps = int(tp["merge_steps"])
        self.per_model = traffic.batch // traffic.workers
        self.g0 = 0                 # global iteration of the next step
        self.next_epoch = 0
        self.start_of: dict = {}    # epoch -> its first global iteration
        self.losses: list = []      # every step's loss, in order
        self.requested: set = set()  # (epoch, it) whose roots were asked
        self.trainer = Trainer(
            graph=CSRGraph(indptr=data.graph.indptr,
                           indices=data.graph.indices),
            labels=data.graph.labels, part=data.part, owner=data.owner,
            local_idx=data.local_idx, table=data.table, cfg=gnn_config(cfg),
            optimizer=adamw(float(o["lr"]), b1=float(o["b1"]),
                            b2=float(o["b2"]), eps=float(o["eps"]),
                            weight_decay=float(o["weight_decay"])),
            params=params0, strategy="hopgnn", pregather=True, pipeline=True,
            mesh=mesh, root_fn=self._roots)
        # the §5.3 controller's own restore-and-freeze: the merge pattern
        # is the traffic's, not chosen from wall times during the run
        self.trainer._resume_pattern = (self.merge_steps, True, None)
        # fit(resume=True) starts at the epoch this returns
        self.trainer._maybe_resume = lambda: self.next_epoch

    def _roots(self, epoch: int, it: int) -> list:
        self.requested.add((epoch, it))
        return self.traffic.per_model(self.start_of[epoch] + it)

    def epoch(self, iters: int):
        """Train global iterations g0 .. g0 + iters - 1 as one epoch."""
        e, t = self.next_epoch, self.trainer
        self.start_of[e] = self.g0
        t.sample_seed_base = self.traffic.sample_seed(self.g0) \
            - e * SEED_STRIDE
        stats = t.fit(epochs=e + 1, iters_per_epoch=iters,
                      batch_per_model=self.per_model, resume=True)
        if [s.epoch for s in stats] != [e] \
                or len(stats[0].iter_losses) != iters:
            raise HarnessError(
                f"fit was to train epoch {e} alone, {iters} iterations; it "
                f"trained epochs {[s.epoch for s in stats]}, "
                f"{sum(len(s.iter_losses) for s in stats)} iterations")
        st, ctl = stats[0], t.controller
        if (ctl is None or not ctl.frozen
                or ctl.pattern_steps != self.merge_steps
                or st.num_steps != self.merge_steps):
            raise HarnessError(
                f"merge pattern not fixed at {self.merge_steps} steps: "
                f"epoch {e} ran {st.num_steps}, controller "
                + ("absent" if ctl is None else
                   f"at {ctl.pattern_steps}, frozen {ctl.frozen}"))
        self.losses.extend(st.iter_losses)
        self.next_epoch += 1
        self.g0 += iters
        return st

    def first_steps(self, b1: float) -> dict:
        """Iterations 0-2: the first gradient as AdamW's first moment
        holds it after one step, and the parameters after three."""
        import jax
        self.epoch(1)
        mu = jax.device_get(self.trainer.opt_state.mu)
        self.epoch(2)
        return {"grad": jax.tree.map(lambda m: np.asarray(m) / (1 - b1), mu),
                "params3": jax.device_get(self.trainer.params)}


def warm_up(training: Training, iters: int) -> float:
    """One warm-up epoch; returns its steady seconds per iteration."""
    st = training.epoch(iters)
    return st.steady_time_s / iters


def window(training: Training, iters: int) -> dict:
    """One epoch of ``iters`` iterations; both ends on synced devices
    (the previous epoch and this one end in ``block_until_ready``)."""
    from repro.core import distributed as engine
    tr0 = engine.trace_count()
    g = training.g0
    t_open = time.perf_counter()
    st = training.epoch(iters)
    t_close = time.perf_counter()
    # an iteration whose roots the program never asked for trained on a
    # plan it did not build for it
    e = training.next_epoch - 1
    unplanned = sum((e, it) not in training.requested for it in range(iters))
    failed = sum(not math.isfinite(v) or (e, it) not in training.requested
                 for it, v in enumerate(st.iter_losses))
    if st.epoch_attempts > 1 or st.rollbacks or st.degradations:
        failed = iters
    return {"t_open": t_open, "t_close": t_close, "seconds": t_close - t_open,
            "iters": iters, "failed": failed, "unplanned": unplanned,
            "remote_rows": st.remote_rows,
            "iterations": list(range(g, g + iters)),
            "traces": engine.trace_count() - tr0, "merge_steps": st.num_steps}


def traced_window(training: Training, iters: int):
    """:func:`window` under the profiler and the program's span tracer."""
    import jax
    from repro.obs import trace as obs
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    TRACE_DIR.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    obs.enable(capacity=1 << 16)
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(ANCHOR):
            anchor = time.perf_counter_ns()
            w = window(training, iters)
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    pc = (int(w["t_open"] * 1e9), int(w["t_close"] * 1e9))
    # perf_counter and perf_counter_ns share one clock
    spans = [(r.name, r.track, r.t0_ns, r.t1_ns) for r in obs.records()
             if r.kind == "X" and r.t1_ns >= pc[0] and r.t0_ns <= pc[1]]
    record = tracereduce.record_from_profile(str(TRACE_DIR), ANCHOR, anchor,
                                             spans, pc)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    dispatches = [t0 for n, _, t0, _ in spans if n == "dispatch"]
    if dispatches:
        w["fill_s"] = (min(dispatches) - pc[0]) / 1e9
    return w, record


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def reference_check(cell: dict, data, traffic: Traffic, params0_host,
                    prog: dict) -> dict:
    """The reference's first three steps from the same start, compared
    with the program's (``reference.compare``)."""
    import jax
    cfg = cell["config"]
    ref = reference.Reference(cfg, data.graph, data.owner, data.local_idx,
                              data.rows)
    batches = [(traffic.roots(g), traffic.sample_seed(g))
               for g in range(reference.FIRST_STEPS)]
    r = reference.trajectory(ref, cfg, jax.device_put(params0_host), batches)
    return reference.compare(prog, r, params0_host)


def train_run(training: Training, cfg: dict, tp: dict, seconds: float,
              trace: bool, on_setup=None):
    """Set-up's first steps and warm-up, then the window of as many
    iterations as fill ``seconds`` at the warm-up's pace. Returns the
    window, its trace record (or None) and the program's readings for the
    check; ``on_setup()`` runs as the window is about to open."""
    prog = training.first_steps(float(cfg["optimizer"]["b1"]))
    iter_s = warm_up(training, int(tp["warmup_iters"]))
    iters = max(2, math.ceil(seconds / iter_s))
    if on_setup is not None:
        on_setup()
    if trace:
        w, record = traced_window(training, iters)
    else:
        w, record = window(training, iters), None
    return w, record, prog


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, say=print) -> dict:
    """One run; returns the result object (see the module doc)."""
    cfg, tp, wl = cell["config"], cell["traffic"], cell["workload"]
    chips = int(wl["chips"])
    devices = check_devices(chips, require_tpu)
    import jax
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev0 = devices[0]
    peak = cells.peaks(dev0.device_kind) if require_tpu else None
    clock = CompileClock()
    label = f"[{cell['name']} seed {seed}]"

    def log(line):
        say(f"{label} {line}")

    data = build_data(cfg, log)
    workers = int(cfg["workers"])
    traffic = Traffic(tp, data.graph.train_vertices(), workers, seed)
    params0 = init_params(seed, cfg["model"])
    params0_host = jax.device_get(params0)
    mesh = jax.make_mesh((workers,), ("data",)) if chips > 1 else None
    training = Training(cfg, tp, data, traffic, params0, mesh)
    del params0
    at = {}

    def on_setup():
        at["setup_s"] = time.perf_counter() - T_START
        at["clock"] = clock.snapshot()
        log(f"set-up {at['setup_s']:.3f} s: {clock.compiles} backend "
            f"compiles, {clock.cache_hits} persistent-cache loads, "
            f"{clock.seconds:.3f} s in compilation")

    span = min(seconds, float(tp["trace_seconds"])) if trace else seconds
    w, record, prog = train_run(training, cfg, tp, span, trace, on_setup)
    c0, c1 = at["clock"], clock.snapshot()
    t = training.trainer
    ctl = t.controller
    log(f"merge pattern: {ctl.pattern_steps} steps, frozen {ctl.frozen}, "
        f"every epoch ran {w['merge_steps']}")
    log(f"window: iterations {w['iterations'][0]}-{w['iterations'][-1]} "
        f"as epoch {training.next_epoch - 1}, {w['seconds']:.6f} s, "
        f"{w['traces']} jit traces, {c1[0] - c0[0]} backend compiles, "
        f"{c1[1] - c0[1]} persistent-cache loads"
        + (f"; first dispatch {w['fill_s']:.6f} s after it opened"
           if "fill_s" in w else ""))
    mem = peak_bytes(devices)
    run = SimpleNamespace(cell=cell, window=w, record=record, chips=chips,
                          traffic=traffic, graph=data.graph, peak=peak,
                          batch_pad=int(t.budget.buckets[w["merge_steps"]][0]),
                          merge_steps=w["merge_steps"], workers=workers,
                          memory_peak_bytes=mem)
    metrics = {}
    if trace:
        for name in cells.per_layer_names(cell["name"]):
            mod = cells.load_metric(name)
            v = mod.read(run)
            if v is not None:
                metrics[name] = {"value": v, "unit": mod.UNIT}
    else:
        roots = w["iters"] * traffic.batch
        metrics["roots_per_s"] = {"value": roots / w["seconds"],
                                  "unit": "roots/s"}
        metrics["setup_s"] = {"value": at["setup_s"], "unit": "s"}
    prog["losses"] = list(training.losses)
    # the program's state goes before the reference takes the chip
    del training, t, ctl
    gc.collect()
    t_ref = time.perf_counter()
    nums = reference_check(cell, data, traffic, params0_host, prog)
    limits = wl["limits"]
    checks = {k: {"value": nums[k], "limit": float(limits[k])}
              for k in reference.CHECKS}
    checks["window_unplanned"] = {"value": w["unplanned"], "limit": 0}
    correct = (all(c["value"] <= c["limit"] for c in checks.values())
               and w["failed"] == 0)
    log(f"reference: {reference.FIRST_STEPS} steps in "
        f"{time.perf_counter() - t_ref:.3f} s; worst gradient leaf "
        f"{nums['grad_leaf']}, worst change leaf {nums['change_leaf']}, "
        f"leaves left out of the change {nums['still_leaves']}")
    out = {"correct": bool(correct), "attempted": w["iters"],
           "failed": w["failed"], "metrics": metrics,
           "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                      "count": chips, "memory_peak_bytes": mem}}
    if record is not None:
        busy = tracereduce.busy_ns(record)
        out["device"]["busy_s"] = sum(busy) / len(busy) / 1e9
        out["device"]["window_s"] = (record["window_ns"][1]
                                     - record["window_ns"][0]) / 1e9
        out["breakdown"] = {
            "device_ops": tracereduce.top_ops(record),
            "idle_gaps": tracereduce.idle_gaps(record, MAIN_TRACK)}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the program under test is missing: {e}",
              file=sys.stderr)
        return 2
    cell = cells.load_cell(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       say=lambda s: print(s, flush=True))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except HarnessError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 4
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
