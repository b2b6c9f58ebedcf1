"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU).

Per the brief: sweep shapes/dtypes and assert_allclose against ref.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops
from repro.kernels.gather_agg import gather_agg, gather_rows
from repro.kernels.linattn import linattn_chunked
from repro.kernels.ref import gather_agg_ref, gather_rows_ref, linattn_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,d", [(16, 128), (64, 256), (33, 96)])
def test_gather_rows_sweep(rows, d, dtype):
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((rows, d)), dtype)
    idx = jnp.asarray(rng.integers(0, rows, 29), jnp.int32)
    out = gather_rows(table, idx, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(gather_rows_ref(table, idx),
                                          np.float32), rtol=1e-6)


@pytest.mark.parametrize("reduce", ["sum", "mean", "max"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,f,d", [(8, 4, 128), (17, 10, 128), (5, 3, 64)])
def test_gather_agg_sweep(n, f, d, reduce, dtype):
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.standard_normal((40, d)), dtype)
    idx = jnp.asarray(rng.integers(0, 40, (n, f)), jnp.int32)
    out = gather_agg(table, idx, reduce=reduce, interpret=True)
    ref = gather_agg_ref(table, idx, reduce=reduce)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dim_splits_lane_tiling(dtype):
    """The kernels DMA whole rows of 32-bit words padded to lane tiles:
    every width maps to a (R, 1, w) view with w % LANE == 0, 16-bit rows
    pack two values per word, and the view round-trips bit-exactly."""
    from repro.kernels.gather_agg import LANE, _as_words, _from_words
    per_word = 4 // jnp.dtype(dtype).itemsize
    rng = np.random.default_rng(8)
    for d, w in [(128, 128), (256, 256), (96, 128), (192, 256),
                 (300, 384), (600, 640)]:
        table = jnp.asarray(rng.standard_normal((5, d)), dtype)
        words = _as_words(table)
        assert words.shape == (5, 1, -(-w // per_word // LANE) * LANE)
        np.testing.assert_array_equal(
            np.asarray(_from_words(words, 5, table.dtype, d)),
            np.asarray(table))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_rows_d192_lane_split(dtype):
    """d = 192: rows padded to two lane tiles, sliced back bit-exact."""
    rng = np.random.default_rng(6)
    table = jnp.asarray(rng.standard_normal((31, 192)), dtype)
    idx = jnp.asarray(rng.integers(0, 31, 27), jnp.int32)
    out = gather_rows(table, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(gather_rows_ref(table, idx)))


@pytest.mark.parametrize("op", ["rows", "agg"])
def test_gather_spans_grid_steps(op):
    """More indices than one grid step DMAs, ending in a partial block:
    every block gathers its own rows and the tail stops at n."""
    from repro.kernels.gather_agg import ROWS_PER_STEP
    rng = np.random.default_rng(9)
    n = 2 * ROWS_PER_STEP + 37
    table = jnp.asarray(rng.standard_normal((300, 100)), jnp.float32)
    if op == "rows":
        idx = jnp.asarray(rng.integers(0, 300, n), jnp.int32)
        out, ref = (gather_rows(table, idx, interpret=True),
                    gather_rows_ref(table, idx))
    else:
        idx = jnp.asarray(rng.integers(0, 300, (n, 3)), jnp.int32)
        out, ref = (gather_agg(table, idx, interpret=True),
                    gather_agg_ref(table, idx))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


@pytest.mark.parametrize("reduce", ["sum", "mean", "max"])
def test_gather_agg_d192_lane_split(reduce):
    rng = np.random.default_rng(7)
    table = jnp.asarray(rng.standard_normal((23, 192)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 23, (9, 5)), jnp.int32)
    out = gather_agg(table, idx, reduce=reduce, interpret=True)
    ref = gather_agg_ref(table, idx, reduce=reduce)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@given(st.integers(4, 40), st.integers(1, 8), st.integers(0, 100))
@settings(max_examples=15, deadline=None)
def test_gather_agg_property(n, f, seed):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal((23, 128)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 23, (n, f)), jnp.int32)
    out = gather_agg(table, idx, reduce="sum", interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(gather_agg_ref(table, idx, "sum")),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("BH,T,dk,dv,chunk", [
    (2, 64, 16, 16, 16), (3, 128, 32, 64, 64), (1, 96, 8, 8, 32),
])
def test_linattn_kernel_sweep(BH, T, dk, dv, chunk):
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((BH, T, dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((BH, T, dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((BH, T, dv)), jnp.float32)
    w = jnp.asarray(0.6 + 0.39 * rng.random((BH, T, dk)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((BH, dk)), jnp.float32)
    o_ref, s_ref = linattn_ref(q, k, v, w, u)
    o, s = linattn_chunked(q, k, v, w, u, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=5e-4, atol=5e-4)


def test_linattn_jnp_matches_scan_and_is_differentiable():
    rng = np.random.default_rng(3)
    BH, T, dk, dv = 2, 64, 16, 16
    q = jnp.asarray(rng.standard_normal((BH, T, dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((BH, T, dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((BH, T, dv)), jnp.float32)
    w = jnp.asarray(0.7 + 0.29 * rng.random((BH, T, dk)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((dk,)), jnp.float32)
    o_ref, s_ref = linattn_ref(q, k, v, w, u)
    o, s = ops.linattn_chunked_jnp(q, k, v, w, u, chunk=16)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=5e-4, atol=5e-4)
    g = jax.grad(lambda q: ops.linattn_chunked_jnp(q, k, v, w, u)[0].sum())(q)
    assert np.isfinite(np.asarray(g)).all()


def test_linattn_decode_step_consistency():
    """T decode steps == one full-sequence pass (cache-correctness)."""
    rng = np.random.default_rng(4)
    BH, T, dk, dv = 2, 32, 8, 8
    q = jnp.asarray(rng.standard_normal((BH, T, dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((BH, T, dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((BH, T, dv)), jnp.float32)
    w = jnp.asarray(0.7 + 0.29 * rng.random((BH, T, dk)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((dk,)), jnp.float32)
    o_ref, s_ref = linattn_ref(q, k, v, w, u)
    S = jnp.zeros((BH, dk, dv))
    outs = []
    for t in range(T):
        o_t, S = ops.linattn_step(q[:, t], k[:, t], v[:, t], w[:, t], u, S)
        outs.append(o_t)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(o_ref), rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(S), np.asarray(s_ref),
                               rtol=5e-4, atol=5e-4)


def test_ops_dispatch_cpu_defaults_to_ref():
    """On CPU the ops layer must route to the jnp reference (fast), with
    force_kernel exercising the interpreted Pallas path."""
    rng = np.random.default_rng(5)
    table = jnp.asarray(rng.standard_normal((10, 128)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 10, (4, 3)), jnp.int32)
    a = ops.gather_agg(table, idx)
    b = ops.gather_agg(table, idx, force_kernel=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


@given(st.integers(1, 4), st.integers(1, 4), st.integers(2, 6),
       st.integers(0, 50))
@settings(max_examples=10, deadline=None)
def test_linattn_property_random_shapes(bh, chunks_, dk_pow, seed):
    """Hypothesis sweep: chunked kernel == token scan for random shapes."""
    rng = np.random.default_rng(seed)
    dk = 2 ** dk_pow
    chunk = 8
    T = chunk * chunks_
    q = jnp.asarray(rng.standard_normal((bh, T, dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((bh, T, dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bh, T, dk)), jnp.float32)
    w = jnp.asarray(0.6 + 0.39 * rng.random((bh, T, dk)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((dk,)), jnp.float32)
    o_ref, s_ref = linattn_ref(q, k, v, w, u)
    o, s = linattn_chunked(q, k, v, w, u, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=2e-3, atol=2e-3)


def test_gather_rows_used_by_engine(partitioned):
    """The device engine's feature gather must round-trip through the
    kernels.ops dispatch layer (integration of the Pallas path)."""
    import repro.core.distributed as dist
    import inspect
    src = inspect.getsource(dist._shard_grads)
    assert "ops.gather_rows" in src
