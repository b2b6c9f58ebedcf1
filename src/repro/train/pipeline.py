"""Async device pipeline (PR 5 tentpole): keep the device saturated.

BENCH_end_to_end.json showed steady *wall* time ~27× steady *device* time —
pure host overhead: an eager per-leaf optimizer update (tens of op
dispatches per iteration), a blocking ``float(loss)`` sync every step, and
a fresh host→device conversion of every plan array on every call. This
module closes that gap with three cooperating pieces:

* **Fused dispatch** — the Trainer steps through
  ``repro.core.distributed.get_compiled_train_step``: iteration + optimizer
  update as ONE compiled program with ``params``/``opt_state`` donation.
  One dispatch per iteration replaces the grads round-trip plus the eager
  update chain.
* **Non-blocking loop** (:func:`run_pipelined_epoch`) — losses stay on
  device and are synced at the epoch boundary, so the host races ahead
  building and uploading plans while the device executes. Backpressure:
  every ``loss_sync_iters`` dispatches (Trainer default 16; 0 disables)
  the loop blocks on the newest loss, bounding how many in-flight
  iterations — each pinning its committed plan buffers — can queue on a
  device-bound config.
* **Plan upload double-buffering** (:class:`PlanUploader`) — the plan
  prefetch thread ``device_put``s plan i+1's device args into ping-pong
  slots while plan i executes, and stamps the plan (``plan.committed``) so
  the engine's arg-prep fast path skips the per-leaf conversion walk on the
  critical path. Slots alternate so the upload for i+1 never retires the
  buffers iteration i is still consuming; shape stability against the
  ShapeBudget bucket is asserted (a shape change would mean a retrace).
* **K-stacking** (optional, ``pipeline_stack=K``) — K same-bucket plans are
  stacked on a leading axis and the fused step is ``lax.scan``-ed over
  them: one dispatch per K iterations, for regimes where per-iteration
  device time is smaller than dispatch overhead.

Timing semantics (this changes what EpochStats fields mean in pipelined
mode): per-iteration wall times are *dispatch* times — the device has not
necessarily finished when the call returns. Steady-state time is therefore
measured on a synced window: the epoch's dispatch loop runs free, a
``block_until_ready`` closes the window, and the window wall over its
iteration count is the steady per-iteration estimate. Whenever a dispatch
(re)traces, the window restarts *after* a sync — so the estimate stays
compile-free and the §5.3 merging controller keeps getting the signal the
Trainer promised it (see repro.core.merging).

Donation contract: the fused step donates params/opt_state. The Trainer
therefore owns its parameter buffers — caller-supplied initial params are
copied once at construction — and always continues from the returned
trees. Never hold a reference to a pre-step params tree across a step.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, List, Optional

import jax
import numpy as np

from repro.core import distributed as engine
from repro.obs.trace import span as obs_span


class PlanUploader:
    """Ping-pong host→device committer for IterationPlan device args.

    ``commit(plan)`` runs on the plan prefetch thread: it ``device_put``s
    the plan's device_args tree (and the f32 denom scalar) and stamps the
    plan (``plan.committed``) for the engine's arg-prep fast path. The
    ping-pong pairing is realized by the in-flight plans themselves: plan
    i's committed tree is still referenced (and being consumed by the
    device) while plan i+1's upload lands in its own fresh buffers, so an
    upload can never retire arrays the previous dispatch still needs.

    Shape discipline: within one merge pattern every upload must carry the
    same shape signature (uploads never imply a retrace). Deviations are
    counted in ``shape_changes`` (raised under ``strict``) — a legitimate
    change exists only at an explicit budget re-bucket; with ``budget``
    given, every committed plan is also checked against the ShapeBudget
    bucket it claims to be built under, which updates the expected
    signature exactly when the bucket itself grew.
    """

    def __init__(self, budget=None, strict: bool = False, view=None,
                 sharding=None):
        self.budget = budget
        self.strict = strict
        self.view = view               # MembershipView (world-stale refusal)
        self.sharding = sharding       # per-shard placement under a mesh
        self._sigs: dict = {}          # pattern (num_steps) -> signature
        self._buckets: dict = {}       # pattern -> bucket_shapes snapshot
        self.uploads = 0
        self.shape_changes = 0

    def commit(self, plan) -> None:
        if self.view is not None:
            # refuse to ship a dead world's bytes to the device: a plan
            # stamped under an older membership generation must not commit
            # (repro.membership; the dispatch boundary re-checks, but the
            # upload is the first place stale buffers would be staged)
            ei = getattr(plan, "epoch_it", (-1, -1))
            self.view.check_generation(getattr(plan, "generation", -1),
                                       epoch=ei[0], it=ei[1])
        expect = None
        if self.budget is not None:
            expect = self.budget.bucket_shapes(plan.num_steps)
            if expect is not None:
                bp, rm, cm, lm = expect
                l_max = getattr(plan, "l_max", 0)
                if (plan.batch_pad, plan.r_max) != (bp, rm) \
                        or plan.c_max not in (0, cm) \
                        or l_max not in (0, lm):
                    raise AssertionError(
                        f"plan shapes ({plan.batch_pad}, {plan.r_max}, "
                        f"{plan.c_max}, {l_max}) drifted from budget bucket "
                        f"({bp}, {rm}, {cm}, {lm}) for pattern "
                        f"{plan.num_steps}")
        dev = jax.tree.map(lambda x: engine._as_device(x, self.sharding),
                           plan.device_args())
        denom = jax.device_put(np.float32(plan.global_batch))
        sig = engine._shape_sig(dev)
        key = plan.num_steps
        prev = self._sigs.get(key)
        if prev is not None and prev != sig:
            if self._buckets.get(key) != expect:
                # explicit budget re-bucket: the new signature is the
                # expected one from here on (one retrace, counted by the
                # engine trace log, not a stability violation)
                pass
            else:
                self.shape_changes += 1
                if self.strict:
                    raise AssertionError(
                        f"upload shape change within pattern {key}: "
                        f"{prev} -> {sig}")
        self._sigs[key] = sig
        self._buckets[key] = expect
        plan.committed = {"dev": dev, "denom": denom}
        self.uploads += 1


def stack_committed(plans):
    """Stack K plans' device args on a new leading axis for the scanned
    fused step. Committed plans stack their already-resident buffers
    (device-side stack, no host copy); uncommitted ones are uploaded
    leaf-by-leaf first."""
    import jax.numpy as jnp
    devs, denoms = [], []
    for p in plans:
        if p.committed is not None:
            devs.append(p.committed["dev"])
            denoms.append(p.committed["denom"])
        else:
            devs.append(jax.tree.map(engine._as_device, p.device_args()))
            denoms.append(jnp.asarray(float(p.global_batch), jnp.float32))
    dev_stack = jax.tree.map(lambda *xs: jnp.stack(xs), *devs)
    return dev_stack, jnp.stack(denoms)


@dataclasses.dataclass
class EpochRunResult:
    """What one epoch's iteration loop hands back to Trainer.fit —
    produced by both the pipelined loop here and the Trainer's synchronous
    loop, so fit() assembles EpochStats identically for both."""

    losses: List[float]          # per-iteration losses, in order
    wall_s: float                # dispatch-loop wall incl. final sync
    steady_iter_s: Optional[float]   # compile-free synced-window estimate
    #                                  (None: every window contained a trace)
    dispatch_s: float            # host time spent inside dispatch calls
    traces: int                  # engine trace-log delta over the epoch
    remote_rows: int
    cache_hit_rows: int
    num_steps: int
    # --- streamed feature path (repro.features; zeros when resident) ---
    tier1_rows: int = 0          # host hot-tier rows served to plan gathers
    tier2_rows: int = 0          # backing/mmap rows served (hot-tier misses)
    upload_bytes: int = 0        # plan-carried feature bytes shipped to dev


def run_pipelined_epoch(trainer, epoch: int, iters: int,
                        batch_per_model: int, submit: Callable,
                        stack: int = 1,
                        loss_sync_iters: int = 0) -> EpochRunResult:
    """One epoch of non-blocking fused dispatch.

    ``submit(fn, *args)`` is the Trainer's plan-prefetch submitter (thread
    pool or inline). Up to ``stack + 1`` plan builds are kept in flight so
    a K-stacked dispatch never starves; each build commits its device
    upload on the prefetch thread (PlanUploader), overlapping the transfer
    with device execution of the previous dispatch.
    """
    K = max(1, int(stack))
    tc_start = engine.trace_count()
    t_epoch = time.perf_counter()

    futs: deque = deque()          # (it, future) pairs, in order
    next_it = 0
    done = 0

    def top_up(minimum: int = 0) -> None:
        nonlocal next_it
        while next_it < iters and (len(futs) < K + 1
                                   or next_it < done + minimum):
            futs.append((next_it, submit(trainer.build_plan, epoch,
                                         next_it, batch_per_model)))
            next_it += 1

    top_up(minimum=1)
    raw_losses: list = []
    remote = hits = 0
    t1 = t2 = up = 0
    num_steps = 0
    dispatch_s = 0.0
    window_t: Optional[float] = None
    window_iters = 0
    steady: Optional[float] = None
    since_sync = 0
    while done < iters:
        k = min(K, iters - done)
        top_up(minimum=k)
        # _plan_result applies the stall deadline (a wedged prefetch
        # thread raises StallError instead of hanging the epoch) and
        # re-raises a supervised build failure with its (epoch, it)
        plans = []
        for _ in range(k):
            it_i, fut = futs.popleft()
            with obs_span("plan.wait", epoch=epoch, it=it_i):
                plans.append(trainer._plan_result(fut, epoch, it_i))
        top_up()
        if window_t is None:
            # the window opens at the first dispatch, after the (serial)
            # first plan build — plan waits *inside* the window are real
            # pipeline stalls and belong in the steady estimate
            window_t = time.perf_counter()
        tc0 = engine.trace_count()
        td0 = time.perf_counter()
        # guarded dispatch: pending background errors surface here (the
        # "next dispatch boundary" contract) and transient comm faults
        # retry during argument staging, pre-donation
        with obs_span("dispatch", epoch=epoch, it=done):
            loss = trainer._dispatch(plans, epoch, done)
        dispatch_s += time.perf_counter() - td0
        raw_losses.append(loss)
        for p in plans:
            remote += p.remote_rows_exact
            hits += p.cache_hit_rows
            ts = getattr(p, "tier_stats", None)
            if ts:
                t1 += ts["tier1_rows"]
                t2 += ts["tier2_rows"]
                up += ts["upload_bytes"]
        num_steps = plans[-1].num_steps
        done += k
        since_sync += k
        if engine.trace_count() > tc0:
            # this dispatch (re)traced: drain the queue and restart the
            # steady window after the sync so compile time never leaks
            # into the merging controller's signal
            with obs_span("trace.sync", epoch=epoch, it=done - 1):
                jax.block_until_ready(trainer.params)
            window_t = time.perf_counter()
            window_iters = 0
        else:
            window_iters += k
        if loss_sync_iters and since_sync >= loss_sync_iters:
            # device-time reconciliation point: this synced window (and
            # the epoch-boundary one below) is where device execution
            # becomes visible to the host timeline — dispatch spans only
            # measure host-side enqueue in the non-blocking loop
            with obs_span("loss.sync", epoch=epoch, it=done - 1):
                jax.block_until_ready(loss)    # queue-depth throttle
            # deferred-loss NaN/Inf guard: this window's loss is on host
            # now — divergence is detected here, not an epoch later
            trainer._check_finite(loss, epoch, done - 1)
            since_sync = 0
    with obs_span("loss.sync", epoch=epoch, it=iters - 1, boundary=True):
        jax.block_until_ready(trainer.params)
    t_end = time.perf_counter()
    if window_iters:
        steady = (t_end - window_t) / window_iters
    losses = [float(v) for l in raw_losses
              for v in np.atleast_1d(np.asarray(l))]
    return EpochRunResult(losses=losses, wall_s=t_end - t_epoch,
                          steady_iter_s=steady, dispatch_s=dispatch_s,
                          traces=engine.trace_count() - tc_start,
                          remote_rows=remote, cache_hit_rows=hits,
                          num_steps=num_steps, tier1_rows=t1, tier2_rows=t2,
                          upload_bytes=up)
