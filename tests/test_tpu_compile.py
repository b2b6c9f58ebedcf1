"""Compile the gather kernels and the training step for a TPU v5e that is
described, not attached (``jax.experimental.topologies``).

Interpret mode (tests/test_kernels.py) checks what the kernels compute;
only the chip's own compiler says whether Mosaic accepts their block
shapes, DMAs and SMEM use at real widths. Each test compiles one program
and asserts that the Pallas kernel (``tpu_custom_call``) is in it.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, so under several test
workers only the worker running this file loads it.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.gather_agg import gather_agg, gather_rows

WIDTHS = [100, 128, 600]        # products, arxiv, the 600-d web graphs
TABLE_ROWS = 65536
N_IDX = 8192


@pytest.fixture(scope="module")
def one_chip():
    import os
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("d", WIDTHS)
def test_gather_rows_compiles_for_v5e(one_chip, d):
    text = _compiled_text(gather_rows,
                          _sds((TABLE_ROWS, d), jnp.float32, one_chip),
                          _sds((N_IDX,), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d", WIDTHS)
def test_gather_agg_compiles_for_v5e(one_chip, d):
    text = _compiled_text(gather_agg,
                          _sds((TABLE_ROWS, d), jnp.float32, one_chip),
                          _sds((N_IDX, 10), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


def test_ops_dispatch_picks_kernel_for_tpu(one_chip):
    """The ops layer chooses by the platform the program is compiled for:
    compiled for the TPU it holds the kernel although this process's
    default backend is the CPU."""
    text = _compiled_text(ops.gather_rows,
                          _sds((TABLE_ROWS, 100), jnp.float32, one_chip),
                          _sds((N_IDX,), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


# the FiLM step emulates two shards, not four: its 11-hop program takes
# half as long to compile, and still holds one kernel per (shard, hop)
@pytest.mark.parametrize("model, layers, fanout, n",
                         [("sage", 2, 10, 4), ("film", 10, 2, 2)],
                         ids=["sage", "film"])
def test_fused_train_step_holds_kernel(one_chip, model, layers, fanout, n):
    """The fused train step (emulated shards, pregather) compiled for the
    TPU gathers every hop with the Pallas kernel: one call per (shard,
    hop), also over the 11 hops of the 10-layer FiLM tree, whose layers'
    ops carry their ``film.edges`` and ``film.nodes`` scopes."""
    from repro.core import distributed as engine
    from repro.models.gnn import GNNConfig
    from repro.models.gnn.models import init_gnn
    from repro.obs.scopes import FILM_EDGES, FILM_NODES
    from repro.optim import adamw
    cfg = GNNConfig(model=model, num_layers=layers, hidden_dim=128,
                    feature_dim=100, num_classes=47, fanout=fanout)
    opt = adamw(1e-3)
    t, bp, r_max = 2, 16, 512
    params = jax.eval_shape(lambda: init_gnn(jax.random.PRNGKey(0), cfg))

    def on_chip(tree):
        return jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), tree)
    dev = dict(req=_sds((n, n, r_max), jnp.int32, one_chip), step_req=None,
               hop_idx=[_sds((n, t, bp * fanout ** h), jnp.int32, one_chip)
                        for h in range(cfg.num_layers + 1)],
               labels=_sds((n, t, bp), jnp.int32, one_chip),
               weights=_sds((n, t, bp), jnp.float32, one_chip))
    fn = engine.get_compiled_train_step(cfg, True, opt)
    text = fn.lower(on_chip(params), on_chip(jax.eval_shape(opt.init, params)),
                    _sds((n, 4096, 100), jnp.float32, one_chip),
                    _sds((n, 0, 100), jnp.float32, one_chip), dev,
                    _sds((), jnp.float32, one_chip)).compile().as_text()
    # one kernel per (shard, hop)
    assert text.count("tpu_custom_call") == n * (cfg.num_layers + 1)
    if model == "film":
        names = re.findall(r'op_name="([^"]*)"', text)
        for scope in (FILM_EDGES, FILM_NODES):
            assert any(f"/{scope}/" in name for name in names), scope
