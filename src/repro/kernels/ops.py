"""Dispatching wrappers around the Pallas kernels.

Call sites never touch `pallas_call` directly. The gather ops are chosen
by the platform the program is *compiled for* (``lax.platform_dependent``),
not by the process's default backend, so a step compiled for a TPU always
holds the Pallas kernel —

  * TPU      → the Pallas kernel (compiled),
  * CPU/test → the pure-jnp oracle (ref.py), or the kernel in interpret
               mode when ``force_kernel=True`` (how tests exercise it).

Training on the TPU gathers with the kernel: the gathered table holds
input features, which are never differentiated, so the kernel needs no
VJP. Linear attention trains through the differentiable jnp chunked form;
its Pallas kernel serves prefill on the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import gather_agg as _ga
from repro.kernels import linattn as _la
from repro.kernels import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def gather_rows(table: jnp.ndarray, idx: jnp.ndarray,
                force_kernel: bool = False) -> jnp.ndarray:
    """out[i] = table[idx[i]]."""
    other = (functools.partial(_ga.gather_rows, interpret=True)
             if force_kernel else _ref.gather_rows_ref)
    return jax.lax.platform_dependent(table, idx, tpu=_ga.gather_rows,
                                      default=other)


def gather_agg(table: jnp.ndarray, idx: jnp.ndarray, reduce: str = "sum",
               force_kernel: bool = False) -> jnp.ndarray:
    """out[i] = reduce_j table[idx[i, j]] (fused gather + segment reduce)."""
    other = (functools.partial(_ga.gather_agg, reduce=reduce, interpret=True)
             if force_kernel
             else functools.partial(_ref.gather_agg_ref, reduce=reduce))
    return jax.lax.platform_dependent(
        table, idx, tpu=functools.partial(_ga.gather_agg, reduce=reduce),
        default=other)


# ---------------------------------------------------------------------------
# Gated linear attention (RWKV6)
# ---------------------------------------------------------------------------

def linattn_chunked_jnp(q, k, v, w, u, state=None, chunk: int = 64):
    """Differentiable chunked formulation in pure jnp (same math as the
    Pallas kernel; lax.scan over chunks carries the state). Used by the
    RWKV6 *training* path; the Pallas kernel serves prefill on TPU."""
    BH, T, dk = q.shape
    dv = v.shape[-1]
    assert T % chunk == 0, (T, chunk)
    C = chunk
    if state is None:
        state = jnp.zeros((BH, dk, dv), jnp.float32)

    qc = q.reshape(BH, T // C, C, dk).astype(jnp.float32)
    kc = k.reshape(BH, T // C, C, dk).astype(jnp.float32)
    vc = v.reshape(BH, T // C, C, dv).astype(jnp.float32)
    wc = w.reshape(BH, T // C, C, dk).astype(jnp.float32)
    uf = jnp.broadcast_to(u, (BH, dk)).astype(jnp.float32)

    t_idx = jnp.arange(C)[:, None]
    s_idx = jnp.arange(C)[None, :]
    causal = (s_idx < t_idx)

    def chunk_step(S, xs):
        qb, kb, vb, wb = xs                   # (BH, C, *)
        e = jnp.cumprod(wb, axis=1)
        e_prev = e / wb
        q_dec = qb * e_prev
        att = jnp.einsum("btd,bsd->bts", q_dec, kb / e)
        att = jnp.where(causal[None], att, 0.0)
        bonus = jnp.einsum("btd,btd->bt", qb * uf[:, None, :], kb)
        o = (jnp.einsum("btd,bdv->btv", q_dec, S)
             + jnp.einsum("bts,bsv->btv", att, vb)
             + bonus[..., None] * vb)
        e_last = e[:, -1]                     # (BH, dk)
        S = (e_last[..., None] * S
             + jnp.einsum("btd,btv->bdv", kb * (e_last[:, None, :] / e), vb))
        return S, o

    S, o = jax.lax.scan(chunk_step, state,
                        (qc.transpose(1, 0, 2, 3), kc.transpose(1, 0, 2, 3),
                         vc.transpose(1, 0, 2, 3), wc.transpose(1, 0, 2, 3)))
    o = o.transpose(1, 0, 2, 3).reshape(BH, T, dv)
    return o.astype(q.dtype), S


def linattn(q, k, v, w, u, state=None, chunk: int = 64,
            force_kernel: bool = False):
    """RWKV6 gated linear attention over a sequence. Returns (o, S_out)."""
    if state is None and (_on_tpu() or force_kernel):
        return _la.linattn_chunked(q, k, v, w, u, chunk=chunk,
                                   interpret=not _on_tpu())
    return linattn_chunked_jnp(q, k, v, w, u, state=state, chunk=chunk)


def linattn_step(q, k, v, w, u, state):
    """Single-token decode update.

    q,k,w: (BH, dk); v: (BH, dv); u: (dk,) or (BH, dk);
    state: (BH, dk, dv) f32. Returns (o: (BH, dv), new_state)."""
    qf, kf, vf, wf = (x.astype(jnp.float32) for x in (q, k, v, w))
    uf = jnp.broadcast_to(u, q.shape).astype(jnp.float32)
    bonus = jnp.sum(qf * uf * kf, axis=-1, keepdims=True)      # (BH, 1)
    o = jnp.einsum("bd,bdv->bv", qf, state) + bonus * vf
    new_state = wf[..., None] * state + kf[..., None] * vf[:, None, :]
    return o.astype(q.dtype), new_state
