"""The harness end to end on the CPU at a tiny size: it refuses to run
without a TPU or without the program, its generator keeps its counts,
its check passes the program, and it fails the control and every fault
a training cell can have, planted under the timed path; a window
iteration trained on a plan not built for it fails too. A run in which the program does
not keep the cell's merge pattern or epoch numbering ends without a
result."""
import copy
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cell as cells  # noqa: E402
from bench import graphgen, reference  # noqa: E402

CELLS = ("sage-products.train", "gat-in2004.train")
# limits of the smallest cell the benchmark checks
LIMITS = cells.load_cell(CELLS[0])["workload"]["limits"]
TINY = {
    "name": "tiny",
    "workload": {"chips": 1, "limits": LIMITS},
    "config": {
        "workers": 4,
        "model": {"layer": "sage", "num_layers": 2, "hidden_dim": 16,
                  "fanout": 4, "feature_dim": 24, "classes": 5, "heads": 4},
        "optimizer": {"lr": 0.003, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
                      "weight_decay": 0.0},
        "graph": {"vertices": 6000, "edges": 60000, "data_seed": 3,
                  "p_intra": 0.85, "classes": 5, "train_fraction": 0.2}},
    "traffic": {"roots_per_iteration": 64, "merge_steps": 4,
                "warmup_iters": 2, "trace_seconds": 1},
}
SEED = 2 ** 33 + 5


def _run_script(args, cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_bench_run_refuses_without_tpu():
    r = _run_script(["--workload", "sage-products.train", "--seed", "1",
                     "--seconds", "1", "--trace", "0"], ROOT,
                    {"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT / "src")})
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert "{" not in r.stdout


def test_bench_run_refuses_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("data", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    r = _run_script(["--workload", "sage-products.train", "--seed", "1",
                     "--seconds", "1", "--trace", "0"], tmp_path,
                    {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "{" not in r.stdout


def test_bench_generator_counts():
    spec = dict(vertices=5000, edges=40001, data_seed=7, p_intra=0.85,
                classes=5, train_fraction=0.1)
    g = graphgen.generate(spec)
    assert (g.num_vertices, g.num_edges, int(g.train_mask.sum())) == \
        (5000, 40000, 512)
    src = np.repeat(np.arange(g.num_vertices), np.diff(g.indptr))
    key = src * g.num_vertices + g.indices
    rev = g.indices.astype(np.int64) * g.num_vertices + src
    assert np.all(np.diff(key) > 0)              # sorted, no duplicates
    assert not np.any(src == g.indices)          # no self loops
    assert np.array_equal(np.sort(rev), key)     # symmetric


@pytest.fixture()
def jax_config():
    import jax
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def _run_tiny(cell=None):
    from bench import run
    from repro.core import distributed as engine
    engine.clear_compile_cache()
    return run.run_cell(copy.deepcopy(cell or TINY), SEED, 0.2, False,
                        require_tpu=False, say=lambda s: None)


def test_bench_check_passes_the_program(jax_config, monkeypatch):
    from repro.train import Trainer
    keys = []
    real = Trainer.build_plan

    def build(self, epoch, it, *a):
        keys.append((epoch, it))
        return real(self, epoch, it, *a)

    monkeypatch.setattr(Trainer, "build_plan", build)
    out = _run_tiny()
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"roots_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {*reference.CHECKS, "window_unplanned"}
    # first steps (1 + 2), warm-up, window: four epochs, no key twice
    assert len(keys) == len(set(keys)) == 3 + 2 + out["attempted"]
    assert sorted({e for e, _ in keys}) == [0, 1, 2, 3]


def _frozen_adamw(*_a, **_k):
    from repro.optim.optimizers import Optimizer, adamw
    real = adamw(1e-3)
    return Optimizer(init=real.init,
                     update=lambda g, s, p: (p, s), key=("frozen-state",))


def _half_batch(monkeypatch):
    from repro.train import Trainer
    real = Trainer._roots_for
    monkeypatch.setattr(Trainer, "_roots_for", lambda self, *a: [
        r[:len(r) // 2] for r in real(self, *a)])


def _no_exchange(monkeypatch):
    import jax.numpy as jnp
    from repro.core.distributed import EmulatedComm
    real = EmulatedComm.exchange_global
    monkeypatch.setattr(EmulatedComm, "exchange_global",
                        lambda self, t, r: jnp.zeros_like(real(self, t, r)))


def _altered_loss(monkeypatch):
    from repro.train import Trainer
    real = Trainer._dispatch_fused
    monkeypatch.setattr(Trainer, "_dispatch_fused",
                        lambda self, plan: real(self, plan) * 1.01)


def _in_window(monkeypatch) -> list:
    """A flag that is set while the measured window runs."""
    from bench import run
    on: list = []
    real = run.window

    def window(training, iters):
        on.append(True)
        return real(training, iters)

    monkeypatch.setattr(run, "window", window)
    return on


def _plan_reused_by_key(monkeypatch):
    """In the window, the roots planned for an earlier epoch's iteration
    of the same number are served again, as by a plan cache keyed on the
    iteration alone."""
    from repro.train import Trainer
    on = _in_window(monkeypatch)
    real = Trainer._roots_for
    kept: dict = {}

    def roots(self, e, it, b):
        if not (on and it in kept):
            kept[it] = real(self, e, it, b)
        return kept[it]

    monkeypatch.setattr(Trainer, "_roots_for", roots)


FAULTS = {"half_batch": _half_batch, "no_exchange": _no_exchange,
          "altered_loss": _altered_loss,
          "plan_reused_by_key": _plan_reused_by_key}


@pytest.mark.parametrize("fault", ["unchanged_state", *FAULTS])
def test_bench_check_fails_each_fault(fault, monkeypatch, jax_config):
    if fault == "unchanged_state":
        monkeypatch.setattr("repro.optim.adamw", _frozen_adamw)
    else:
        FAULTS[fault](monkeypatch)
    out = _run_tiny()
    assert not out["correct"], (fault, out["checks"])


def _merge_pattern_free(monkeypatch):
    from repro.core.merging import MergingController
    monkeypatch.setattr(MergingController, "restore",
                        lambda self, *a, **k: None)


def _epoch_numbers_ignored(monkeypatch):
    from repro.train import Trainer
    real = Trainer.fit
    monkeypatch.setattr(Trainer, "fit", lambda self, *a, resume=False, **k:
                        real(self, *a, **k))


@pytest.mark.parametrize("breach", [_merge_pattern_free,
                                    _epoch_numbers_ignored],
                         ids=lambda f: f.__name__.strip("_"))
def test_bench_run_ends_when_the_program_leaves_the_cell(breach, monkeypatch,
                                                         jax_config):
    from bench import run
    breach(monkeypatch)
    with pytest.raises(run.HarnessError):
        _run_tiny()


def _tiny_like(name: str) -> dict:
    """TINY with the layer and limits of cell ``name``."""
    c = cells.load_cell(name)
    tiny = copy.deepcopy(TINY)
    tiny["config"]["model"]["layer"] = c["config"]["model"]["layer"]
    tiny["workload"]["limits"] = c["workload"]["limits"]
    return tiny


@pytest.mark.parametrize("name", CELLS)
def test_bench_check_fails_the_control(name):
    """The reference with float8 operands in the program's place, against
    each cell's limits."""
    import jax
    from bench.features import init_params
    from bench.run import build_data
    from bench.traffic import Traffic
    tiny = _tiny_like(name)
    cfg, tp = tiny["config"], tiny["traffic"]
    limits = tiny["workload"]["limits"]
    data = build_data(cfg, lambda s: None)
    traffic = Traffic(tp, data.graph.train_vertices(), 4, SEED)
    p0 = jax.device_get(init_params(SEED, cfg["model"]))
    ref = reference.Reference(cfg, data.graph, data.owner, data.local_idx,
                              data.rows)
    batches = [(traffic.roots(g), traffic.sample_seed(g)) for g in range(3)]
    exact = reference.trajectory(ref, cfg, jax.device_put(p0), batches)
    control = reference.trajectory(ref, cfg, jax.device_put(p0), batches,
                                   control=True)
    nums = reference.compare(control, exact, p0)
    assert any(nums[k] > limits[k] for k in limits), nums
