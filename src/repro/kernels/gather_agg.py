"""Pallas TPU kernels: feature-row gather and fused gather+aggregate.

These are the compute hot-spots of LeapGNN's data path (DESIGN.md §2):

* ``gather_words`` — workspace row gather ``out[i] = table[idx[i]]`` from a
  workspace already laid out by :func:`as_words`; the inner op of
  pre-gathering (§5.2) and of every tree-block feature load.
  ``gather_rows`` lays a plain ``(R, d)`` table out and gathers from it.
* ``gather_agg``   — fused neighbor gather + segment reduction over the
  fixed-fanout axis, replacing DGL's SpMM. On GPU this is a scatter-based
  sparse kernel; the TPU-native re-expression uses the *regular* (n, f)
  neighbor-index matrix and accumulates each fanout position into an
  output block resident in VMEM — no atomics (TPU has none), no scatter.

Both kernels move rows with explicit DMAs. The table stays in HBM
(``memory_space=pl.ANY``) laid out as ``(R, 1, w)`` rows of ``w`` 32-bit
words, ``w`` a multiple of the 128-lane tile: Mosaic refuses a DMA of one
row of an ``(R, d)`` array (a one-sublane slice of an (8, 128) tile) and
of a row narrower than a lane tile, but copies a whole ``(1, w)`` row of
the 3-D view. That layout is a padded copy of the whole table, so the
gather takes the workspace already laid out: the caller makes the words
once where it assembles the workspace (``core.distributed``) and gathers
every hop and time step from them. Each grid step takes
:data:`ROWS_PER_STEP` indices as an SMEM block, starts one row DMA per
index into its VMEM output block, and waits for all of them; the output
block is then written back by the pipeline. Index blocks are per step,
so the index count is not bounded by SMEM (1 MiB on v5e), as it is for a
scalar-prefetched index array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs.scopes import KERNEL

LANE = 128  # TPU lane width; rows are padded to whole lane tiles

# Rows DMA'd per grid step. It is also the SMEM index block, which must
# match the 1024-element tiling XLA gives a 1-D int32 array.
ROWS_PER_STEP = 1024


def as_words(table: jnp.ndarray) -> jnp.ndarray:
    """(R, d) table -> (R, 1, w) rows of 32-bit words, w % LANE == 0.
    Rows of 8-/16-bit dtypes are packed into uint32 words."""
    R, d = table.shape
    per_word = 4 // table.dtype.itemsize
    w = -(-d // (LANE * per_word)) * LANE
    t = jnp.pad(table, ((0, 0), (0, w * per_word - d)))
    if per_word > 1:
        t = jax.lax.bitcast_convert_type(t.reshape(R, w, per_word),
                                         jnp.uint32)
    return t.reshape(R, 1, w)


def _from_words(words: jnp.ndarray, n: int, dtype, d: int) -> jnp.ndarray:
    """Inverse of :func:`as_words` for the first ``n`` gathered rows."""
    x = words[:n, 0]
    if x.dtype != dtype:
        x = jax.lax.bitcast_convert_type(x, dtype).reshape(n, -1)
    return x[:, :d]


def _dma_rows(idx_ref, table_hbm, dst_ref, sem, count) -> None:
    """Copy ``table_hbm[idx_ref[r]]`` to ``dst_ref[r]`` for r < count:
    start every row DMA, then wait for all of them on one semaphore."""
    def copy(r, row):
        return pltpu.make_async_copy(table_hbm.at[pl.ds(row, 1)],
                                     dst_ref.at[pl.ds(r, 1)], sem)

    def start(r, carry):
        copy(r, idx_ref[r]).start()
        return carry

    def wait(r, carry):
        copy(r, 0).wait()
        return carry

    jax.lax.fori_loop(0, count, start, 0)
    jax.lax.fori_loop(0, count, wait, 0)


def _padded(idx: jnp.ndarray) -> jnp.ndarray:
    n = idx.shape[-1]
    pad = -n % ROWS_PER_STEP
    return jnp.pad(idx.astype(jnp.int32), [(0, 0)] * (idx.ndim - 1)
                   + [(0, pad)])


# ---------------------------------------------------------------------------
# gather_rows: out[i] = table[idx[i]]
# ---------------------------------------------------------------------------

def _gather_rows_kernel(idx_ref, table_hbm, out_ref, sem, *, n: int):
    count = jnp.minimum(ROWS_PER_STEP, n - pl.program_id(0) * ROWS_PER_STEP)
    _dma_rows(idx_ref, table_hbm, out_ref, sem, count)


def gather_words(words: jnp.ndarray, idx: jnp.ndarray, dtype, d: int,
                 interpret: bool = False) -> jnp.ndarray:
    """words: (R, 1, w) from :func:`as_words` of an (R, d) table of
    ``dtype``; idx: (n,) int32 -> (n, d). The Pallas call runs under the
    ``kernel`` scope; the unpacking of its output does not."""
    n, w = idx.shape[0], words.shape[-1]
    idx_p = _padded(idx)
    with jax.named_scope(KERNEL):
        out = pl.pallas_call(
            functools.partial(_gather_rows_kernel, n=n),
            grid=(idx_p.shape[0] // ROWS_PER_STEP,),
            in_specs=[pl.BlockSpec((ROWS_PER_STEP,), lambda i: (i,),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((ROWS_PER_STEP, 1, w),
                                   lambda i: (i, 0, 0)),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
            out_shape=jax.ShapeDtypeStruct((idx_p.shape[0], 1, w),
                                           words.dtype),
            interpret=interpret,
        )(idx_p, words)
    return _from_words(out, n, dtype, d)


def gather_rows(table: jnp.ndarray, idx: jnp.ndarray,
                interpret: bool = False) -> jnp.ndarray:
    """table: (R, d), idx: (n,) int32 -> (n, d): :func:`gather_words`
    from the table laid out for this one call."""
    return gather_words(as_words(table), idx, table.dtype, table.shape[1],
                        interpret=interpret)


# ---------------------------------------------------------------------------
# gather_agg: out[i] = reduce_j table[idx[i, j]]
# ---------------------------------------------------------------------------

def _gather_agg_kernel(idx_ref, table_hbm, out_ref, rows_ref, sem, *,
                       n: int, fanout: int, reduce: str):
    j = pl.program_id(1)  # fanout position (innermost revisits out block)
    count = jnp.minimum(ROWS_PER_STEP, n - pl.program_id(0) * ROWS_PER_STEP)
    _dma_rows(idx_ref, table_hbm, rows_ref, sem, count)
    row = rows_ref[...]

    @pl.when(j == 0)
    def _init():
        out_ref[...] = row

    @pl.when(j > 0)
    def _acc():
        if reduce == "max":
            out_ref[...] = jnp.maximum(out_ref[...], row)
        else:
            out_ref[...] = out_ref[...] + row

    if reduce == "mean":
        @pl.when(j == fanout - 1)
        def _norm():
            out_ref[...] = out_ref[...] / fanout


def gather_agg(table: jnp.ndarray, idx: jnp.ndarray, reduce: str = "sum",
               interpret: bool = False) -> jnp.ndarray:
    """table: (R, d), idx: (n, f) int32 -> (n, d) reduced over f.

    Grid is (row blocks, f); the output block stays resident in VMEM
    across the f accumulation steps (TPU grids execute sequentially, so
    revisiting an output block is the supported accumulate idiom). The
    index matrix is laid out fanout-major so each step's index block is
    contiguous. Accumulation is f32, so the table is read as f32 rows.
    """
    n, f = idx.shape
    d = table.shape[1]
    words = as_words(table.astype(jnp.float32))
    w = words.shape[-1]
    idx_p = _padded(idx.T).reshape(-1)                 # (f * n_pad,)
    blocks = idx_p.shape[0] // f // ROWS_PER_STEP
    out = pl.pallas_call(
        functools.partial(_gather_agg_kernel, n=n, fanout=f, reduce=reduce),
        grid=(blocks, f),
        in_specs=[pl.BlockSpec((ROWS_PER_STEP,),
                               lambda i, j: (j * blocks + i,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((ROWS_PER_STEP, 1, w),
                               lambda i, j: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((ROWS_PER_STEP, 1, w), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        out_shape=jax.ShapeDtypeStruct((blocks * ROWS_PER_STEP, 1, w),
                                       jnp.float32),
        interpret=interpret,
    )(idx_p, words)
    return _from_words(out, n, jnp.float32, d).astype(table.dtype)
