"""Distribution: shard_map GNN training vs emulation (subprocess with 8
host devices), and sharding-spec construction for every assigned arch."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.configs import ARCH_IDS, get_config, smoke_variant

ROOT = Path(__file__).resolve().parents[1]


def _run_subprocess(code: str, devices: int = 8) -> dict:
    """Run `code` in a fresh interpreter with N host devices; it must print
    a single JSON line starting with RESULT:."""
    prog = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = "
        f"'--xla_force_host_platform_device_count={devices}'\n"
        + textwrap.dedent(code))
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env={"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/root",
             # force the CPU backend: without this, a libtpu install probes
             # GCP instance metadata for ~8 minutes before falling back
             "JAX_PLATFORMS": "cpu"},
        timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for line in out.stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    raise AssertionError(f"no RESULT line in:\n{out.stdout}\n{out.stderr}")


@pytest.mark.slow
def test_shard_map_matches_emulation():
    """The shard_map engine (real all_to_all/psum over 4 devices) must give
    the same loss and gradients as the single-device emulation."""
    res = _run_subprocess("""
        import json
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.graph import make_dataset, ldg_partition
        from repro.graph.partition import shard_features
        from repro.core import plan_iteration, run_iteration
        from repro.models.gnn import GNNConfig, init_gnn

        ds = make_dataset('arxiv', scale=0.02, seed=0)
        n = 4
        part = ldg_partition(ds.graph, n, passes=1)
        table, owner, local_idx = shard_features(ds.features, part, n)
        rng = np.random.default_rng(0)
        tv = ds.train_vertices()
        roots = [rng.choice(tv, 8, replace=False) for _ in range(n)]
        plan = plan_iteration(ds.graph, ds.labels, part, owner, local_idx,
                              table.shape[1], roots, num_layers=2, fanout=4,
                              strategy='hopgnn', pregather=True,
                              sample_seed=3)
        cfg = GNNConfig(model='sage', num_layers=2, hidden_dim=16,
                        feature_dim=ds.feature_dim,
                        num_classes=ds.num_classes, fanout=4)
        params = init_gnn(jax.random.PRNGKey(0), cfg)

        g_emu, l_emu = run_iteration(params, table, plan, cfg, mesh=None)
        mesh = jax.make_mesh((n,), ('data',))
        g_map, l_map = run_iteration(params, table, plan, cfg, mesh=mesh)
        dmax = max(float(jnp.abs(a - b).max()) for a, b in
                   zip(jax.tree.leaves(g_emu), jax.tree.leaves(g_map)))
        print('RESULT:' + json.dumps(
            {'l_emu': float(l_emu), 'l_map': float(l_map), 'dmax': dmax}))
    """)
    assert abs(res["l_emu"] - res["l_map"]) < 1e-5
    assert res["dmax"] < 1e-5


@pytest.mark.slow
def test_shard_map_matches_emulation_per_step():
    """Per-step mode with the batched index exchange: real collectives over
    4 devices must match the single-device emulation bit-for-bit."""
    res = _run_subprocess("""
        import json
        import numpy as np, jax, jax.numpy as jnp
        from repro.graph import make_dataset, ldg_partition
        from repro.graph.partition import shard_features
        from repro.core import plan_iteration, run_iteration
        from repro.models.gnn import GNNConfig, init_gnn

        ds = make_dataset('arxiv', scale=0.02, seed=0)
        n = 4
        part = ldg_partition(ds.graph, n, passes=1)
        table, owner, local_idx = shard_features(ds.features, part, n)
        rng = np.random.default_rng(0)
        tv = ds.train_vertices()
        roots = [rng.choice(tv, 8, replace=False) for _ in range(n)]
        plan = plan_iteration(ds.graph, ds.labels, part, owner, local_idx,
                              table.shape[1], roots, num_layers=2, fanout=4,
                              strategy='hopgnn', pregather=False,
                              sample_seed=3)
        cfg = GNNConfig(model='sage', num_layers=2, hidden_dim=16,
                        feature_dim=ds.feature_dim,
                        num_classes=ds.num_classes, fanout=4)
        params = init_gnn(jax.random.PRNGKey(0), cfg)

        g_emu, l_emu = run_iteration(params, table, plan, cfg, mesh=None)
        mesh = jax.make_mesh((n,), ('data',))
        g_map, l_map = run_iteration(params, table, plan, cfg, mesh=mesh)
        dmax = max(float(jnp.abs(a - b).max()) for a, b in
                   zip(jax.tree.leaves(g_emu), jax.tree.leaves(g_map)))
        print('RESULT:' + json.dumps(
            {'l_emu': float(l_emu), 'l_map': float(l_map), 'dmax': dmax}))
    """)
    assert res["l_emu"] == res["l_map"]
    assert res["dmax"] == 0.0


def test_per_step_iteration_collective_counts():
    """Acceptance: the batched index exchange makes unfolded per-step mode
    run exactly T+1 all_to_alls per iteration (T feature returns + 1
    batched index shipment; the seed ran 2T); folding the feature returns
    (serve_features_batched) brings it to exactly 2 — the same count as
    pregather mode. Trace-only (jax.make_jaxpr — no compile, no
    execution), so the subprocess is cheap enough for the tier-1 lane."""
    res = _run_subprocess("""
        import json
        import numpy as np, jax, jax.numpy as jnp
        from repro.graph import make_dataset, ldg_partition
        from repro.graph.partition import shard_features
        from repro.core import plan_iteration
        from repro.core import distributed as engine
        from repro.models.gnn import GNNConfig, init_gnn

        ds = make_dataset('arxiv', scale=0.01, seed=0)
        n = 4
        part = ldg_partition(ds.graph, n, passes=1)
        table, owner, local_idx = shard_features(ds.features, part, n)
        rng = np.random.default_rng(0)
        tv = ds.train_vertices()
        roots = [rng.choice(tv, 4, replace=False) for _ in range(n)]
        cfg = GNNConfig(model='sage', num_layers=2, hidden_dim=8,
                        feature_dim=ds.feature_dim,
                        num_classes=ds.num_classes, fanout=2)
        params = init_gnn(jax.random.PRNGKey(0), cfg)
        mesh = jax.make_mesh((n,), ('data',))
        cache = jnp.zeros((n, 0, ds.feature_dim), jnp.float32)
        out = {}
        for key, pregather, fold in (('per_step', False, False),
                                     ('per_step_folded', False, True),
                                     ('pregather', True, False)):
            plan = plan_iteration(ds.graph, ds.labels, part, owner,
                                  local_idx, table.shape[1], roots,
                                  num_layers=2, fanout=2,
                                  strategy='hopgnn', pregather=pregather,
                                  sample_seed=3)
            fn = engine.get_compiled_iteration(cfg, pregather, mesh=mesh,
                                               fold_returns=fold)
            dev = jax.tree.map(jnp.asarray, plan.device_args())
            c = engine.collective_counts(fn, params, jnp.asarray(table),
                                         cache, dev,
                                         jnp.asarray(1.0, jnp.float32))
            out[key] = c.get('all_to_all', 0)
            out['T'] = plan.num_steps
        print('RESULT:' + json.dumps(out))
    """, devices=4)
    assert res["per_step"] == res["T"] + 1      # was 2T before batching
    assert res["per_step_folded"] == 2          # T feature returns folded
    assert res["pregather"] == 2


def test_trainer_mesh_places_each_shard_on_its_device():
    """Trainer under a four-device mesh: the feature table, the cache table
    and every committed plan array hold one shard per device (no reshard
    out of device 0 each step), params are replicated, and losses match
    the emulated run."""
    res = _run_subprocess("""
        import json
        import numpy as np, jax
        from repro.graph import make_dataset, ldg_partition
        from repro.graph.partition import shard_features
        from repro.models.gnn import GNNConfig
        from repro.train import Trainer

        ds = make_dataset('arxiv', scale=0.01, seed=0)
        n = 4
        part = ldg_partition(ds.graph, n, passes=1)
        table, owner, local_idx = shard_features(ds.features, part, n)
        cfg = GNNConfig(model='sage', num_layers=2, hidden_dim=8,
                        feature_dim=ds.feature_dim,
                        num_classes=ds.num_classes, fanout=2)

        def fit(mesh):
            tr = Trainer(graph=ds.graph, labels=ds.labels, part=part,
                         owner=owner, local_idx=local_idx, table=table,
                         cfg=cfg, mesh=mesh,
                         train_vertices=ds.train_vertices())
            stats = tr.fit(epochs=2, iters_per_epoch=2, batch_per_model=4)
            return tr, [v for st in stats for v in st.iter_losses]

        _, emulated = fit(None)
        tr, sharded = fit(jax.make_mesh((n,), ('data',)))
        plan = tr.build_plan(2, 0, 4)
        arrays = [tr.table, tr._empty_table()] + jax.tree.leaves(
            plan.committed['dev'])
        split = all(
            len({s.device for s in a.addressable_shards}) == n
            and all(s.data.shape == (1,) + a.shape[1:]
                    for s in a.addressable_shards) for a in arrays)
        replicated = all(a.sharding.is_fully_replicated
                         for a in jax.tree.leaves(tr.params))
        print('RESULT:' + json.dumps({
            'split': split, 'arrays': len(arrays),
            'replicated': replicated,
            'emulated': emulated, 'sharded': sharded}))
    """, devices=4)
    assert res["split"] and res["arrays"] >= 5
    assert res["replicated"]
    assert len(res["sharded"]) == 4
    assert res["sharded"] == pytest.approx(res["emulated"], rel=1e-5)


@pytest.mark.slow
def test_transformer_sharded_train_step_runs():
    """A reduced transformer train step under a real 4×2 mesh with the
    production sharding rules executes and returns finite loss."""
    res = _run_subprocess("""
        import json, dataclasses
        import jax, jax.numpy as jnp
        from repro.configs import get_config, smoke_variant
        from repro.data import make_batch
        from repro.launch import sharding as shd
        from repro.launch.mesh import make_host_mesh
        from repro.launch.train import make_train_step, pick_optimizer
        from repro.models.transformer import init_params
        from repro.models.transformer.common import set_mesh_axes

        cfg = smoke_variant(get_config('qwen2-moe-a2.7b'))
        mesh = make_host_mesh(data=4, model=2)
        set_mesh_axes(dp=('data',), tp=('model',))
        params = init_params(jax.random.PRNGKey(0), cfg)
        p_specs = shd.param_pspecs(params)
        opt = pick_optimizer(cfg)
        opt_state = opt.init(params)
        o_specs = shd.opt_pspecs(opt_state, p_specs)
        batch = make_batch(cfg, 8, 32, seed=0)
        b_specs = shd.batch_pspecs(cfg, mesh, batch)
        step = jax.jit(make_train_step(cfg, opt),
                       in_shardings=(shd.to_shardings(mesh, p_specs),
                                     shd.to_shardings(mesh, o_specs),
                                     shd.to_shardings(mesh, b_specs)),
                       out_shardings=(shd.to_shardings(mesh, p_specs),
                                      shd.to_shardings(mesh, o_specs), None))
        with mesh:
            params2, opt_state, m = step(params, opt_state, batch)
            params3, _, m2 = step(params2, opt_state, batch)
        print('RESULT:' + json.dumps({'loss': float(m['loss']),
                                      'loss2': float(m2['loss'])}))
    """)
    assert res["loss"] > 0 and res["loss2"] > 0
    import math
    assert math.isfinite(res["loss"]) and math.isfinite(res["loss2"])


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_param_pspecs_cover_every_leaf(arch_id):
    """Every parameter leaf gets a PartitionSpec of matching rank, and every
    named axis dim is divisible-or-replicated sanely."""
    from repro.launch.sharding import param_pspecs
    cfg = get_config(arch_id)
    shapes = jax.eval_shape(
        lambda: __import__('repro.models.transformer', fromlist=['m'])
        .init_params(jax.random.PRNGKey(0), cfg))
    specs = param_pspecs(shapes)
    leaves_s, _ = jax.tree.flatten(shapes)
    leaves_p = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert len(leaves_s) == len(leaves_p)
    for sh, sp in zip(leaves_s, leaves_p):
        assert isinstance(sp, jax.sharding.PartitionSpec)
        assert len(sp) <= len(sh.shape), (sh.shape, sp)
        for dim, ax in zip(sh.shape, tuple(sp) + (None,) * 8):
            if ax in ("data", "model"):
                # 16-way shards: dims must be ≥16 or sharding is wasteful
                assert dim % 8 == 0 or dim >= 16, (arch_id, sh.shape, sp)
