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
    text = _compiled_text(lambda t, i: ops.gather_rows(ops.as_words(t), i),
                          _sds((TABLE_ROWS, 100), jnp.float32, one_chip),
                          _sds((N_IDX,), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


# the FiLM step emulates two shards, not four: its 11-hop program takes
# half as long to compile, and still holds one kernel per (shard, hop).
# Each shard holds LOCAL_ROWS + n·R_MAX workspace rows (102,000 and
# 101,000), a count no hop's index vector (16·fanout^h, padded to 1024s)
# has. At a few MB the compiler would stage the words in VMEM once per
# step (copy-start in the loop); at 51 MB per shard, as at real sizes, not.
LOCAL_ROWS, R_MAX = 100_000, 500


@pytest.fixture(scope="module",
                params=[("sage", 2, 10, 4), ("film", 10, 2, 2)],
                ids=["sage", "film"])
def fused_step(one_chip, request):
    """The fused train step (emulated shards, pregather) compiled for the
    TPU: its optimised HLO text, the shard count and the config."""
    from repro.core import distributed as engine
    from repro.models.gnn import GNNConfig
    from repro.models.gnn.models import init_gnn
    from repro.optim import adamw
    model, layers, fanout, n = request.param
    cfg = GNNConfig(model=model, num_layers=layers, hidden_dim=128,
                    feature_dim=100, num_classes=47, fanout=fanout)
    opt = adamw(1e-3)
    t, bp = 2, 16
    params = jax.eval_shape(lambda: init_gnn(jax.random.PRNGKey(0), cfg))

    def on_chip(tree):
        return jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), tree)
    dev = dict(req=_sds((n, n, R_MAX), jnp.int32, one_chip), step_req=None,
               hop_idx=[_sds((n, t, bp * fanout ** h), jnp.int32, one_chip)
                        for h in range(cfg.num_layers + 1)],
               labels=_sds((n, t, bp), jnp.int32, one_chip),
               weights=_sds((n, t, bp), jnp.float32, one_chip))
    fn = engine.get_compiled_train_step(cfg, True, opt)
    text = fn.lower(on_chip(params), on_chip(jax.eval_shape(opt.init, params)),
                    _sds((n, LOCAL_ROWS, 100), jnp.float32, one_chip),
                    _sds((n, 0, 100), jnp.float32, one_chip), dev,
                    _sds((), jnp.float32, one_chip)).compile().as_text()
    return text, n, cfg


def test_fused_train_step_holds_kernel(fused_step):
    """The fused train step compiled for the TPU gathers every hop with
    the Pallas kernel: one call per (shard, hop), also over the 11 hops of
    the 10-layer FiLM tree, whose layers' ops carry their ``film.edges``
    and ``film.nodes`` scopes."""
    from repro.obs.scopes import FILM_EDGES, FILM_NODES
    text, n, cfg = fused_step
    # one kernel per (shard, hop)
    assert text.count("tpu_custom_call") == n * (cfg.num_layers + 1)
    if cfg.model == "film":
        names = re.findall(r'op_name="([^"]*)"', text)
        for scope in (FILM_EDGES, FILM_NODES):
            assert any(f"/{scope}/" in name for name in names), scope


def _computations(text):
    """HLO computation name -> its instruction lines."""
    comps, name = {}, None
    for line in text.splitlines():
        if name is None:
            m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
            if m:
                name, comps[m.group(1)] = m.group(1), []
        elif line == "}":
            name = None
        else:
            comps[name].append(line)
    return comps


# instructions that pass an array along without moving its bytes
_PLUMBING = {"parameter", "get-tuple-element", "tuple", "bitcast", "while"}


def _loop_producers(text, rows):
    """(opcode, line) of every instruction inside a while body, or in a
    computation it calls, whose result has ``rows`` leading rows."""
    comps = _computations(text)
    todo, seen, hits = re.findall(r"body=%([\w.\-]+)", text), set(), []
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in comps[comp]:
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", line)
            m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][\w\-]*)\(", line)
            if (m and m.group(2) not in _PLUMBING
                    and re.search(rf"\[{rows}[,\]]", m.group(1))):
                hits.append((m.group(2), line.strip()[:160]))
    return hits


def test_fused_step_lays_out_workspace_outside_loops(fused_step):
    """Each shard's workspace is laid out in the kernel's words once,
    before the time-step scan: the scan carries the words, and nothing in
    any while body (pad, relayout, copy, fusion) makes an array of the
    workspace's row count. The kernel still runs once per (shard, hop)."""
    text, n, cfg = fused_step
    rows = LOCAL_ROWS + n * R_MAX
    assert re.search(rf"f32\[{rows},1,128\].* while\(", text)
    assert _loop_producers(text, rows) == []
    assert text.count("tpu_custom_call") == n * (cfg.num_layers + 1)
