"""Device engine: execute an IterationPlan under SPMD.

The per-iteration computation is written once against an abstract ``Comm``
interface with two implementations:

* ``ShardComm``   — real collectives (``lax.all_to_all`` / ``psum``) inside
  ``shard_map`` over the mesh's ``"data"`` axis. Used by the launcher, the
  multi-device integration tests, and the dry-run.
* ``EmulatedComm``— the same exchange as pure gathers over globally-stacked
  arrays on a single device. Bit-identical numerics, used by unit tests and
  the CPU benchmark harness (1-core container).

The feature exchange is LeapGNN's pre-gathering (§5.2; the paper titles the
system "HopGNN" but names it LeapGNN in the text) mapped to TPU: one
all_to_all carries the (deduplicated) request indices, a second carries the
feature rows back — the SPMD analogue of the paper's batched gRPC fetch.
Training then scans the iteration's time steps (§5.1), accumulating
gradients, and ends with a single data-parallel gradient reduction.

Remote-feature cache (repro.cache): every iteration body takes a
``(N, c_max, d)`` cache table next to the feature table; the per-shard
workspace is assembled as ``[local | cached | fetched]`` rows, matching the
planner's slot layout. ``c_max = 0`` (the default when no cache is passed)
degenerates to the original two-region workspace.

Per-step collectives: the T index requests ship in ONE batched all_to_all
hoisted ahead of the time-step scan (PR 2). When ``T·r_max`` fits
:data:`FOLD_RETURNS_MAX_TR`, the T feature *returns* are folded into one
batched collective too (``serve_features_batched``): per-step mode then
runs exactly 2 all_to_alls per iteration — the same count as pregather
mode — at the cost of a ``(T, P, r_max, d)`` staging buffer, which is what
the budget flag gates.

Compile-once contract: jitted callables are built once per
``(cfg, pregather, fold_returns, mesh, axis)`` by
:func:`get_compiled_iteration` and reused by every ``run_iteration`` call;
the true global batch size is a *traced* scalar (``denom``), so varying
true batch sizes never retrace. Each (re)trace is appended to a
module-level trace log, which the repro.train Trainer and the regression
tests use to assert the compile-once invariant.

Fused train step (async pipeline, repro.train.pipeline): next to the
grads-returning iteration there is a fused program
``fn(params, opt_state, table, cache, dev, denom) ->
(params', opt_state', loss)`` (:func:`get_compiled_train_step`) that folds
the optimizer update into the same XLA program with buffer donation for
``params``/``opt_state`` — one dispatch per iteration instead of a grads
round-trip plus tens of eager optimizer ops. **Donation contract:** the
caller's ``params``/``opt_state`` buffers are consumed by the call; thread
the returned trees forward and never reuse the inputs. A ``stacked=True``
variant scans the fused step over K same-bucket iterations stacked on a
leading axis, amortizing dispatch when per-iteration device time is tiny.

Argument fast path: :func:`prepare_iteration_args` uploads only host-side
leaves — device-resident tables/caches pass through untouched, and a plan
whose device args were pre-committed by the pipeline uploader
(``plan.committed``) skips the per-leaf conversion walk entirely.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jex_core
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.gnn.models import GNNConfig, gnn_forward, gnn_loss
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace


def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def tree_zeros_like(t):
    return jax.tree.map(jnp.zeros_like, t)


# ---------------------------------------------------------------------------
# Comm backends
# ---------------------------------------------------------------------------

class ShardComm:
    """Real collectives; valid only inside shard_map over ``axis``."""

    def __init__(self, axis: str = "data"):
        self.axis = axis

    def exchange_indices(self, req: jnp.ndarray) -> jnp.ndarray:
        """req: (P, r_max) peer-local indices I want. Returns (P, r_max):
        row p = indices peer p wants from me."""
        return jax.lax.all_to_all(req, self.axis, split_axis=0,
                                  concat_axis=0, tiled=True)

    def exchange_indices_batched(self, step_req: jnp.ndarray) -> jnp.ndarray:
        """step_req: (T, P, r_max) — all T per-step index requests in ONE
        all_to_all (split/concat over the peer axis). Returns (T, P, r_max):
        ``out[t, p]`` = indices peer p wants from me at step t. Hoisting
        this ahead of the time-step scan halves the per-step collective
        count: the scan body only ships features back (T+1 all_to_alls per
        iteration instead of 2T)."""
        return jax.lax.all_to_all(step_req, self.axis, split_axis=1,
                                  concat_axis=1, tiled=True)

    def serve_features(self, table: jnp.ndarray,
                       incoming: jnp.ndarray) -> jnp.ndarray:
        """table: (local_rows, d); incoming: (P, r_max) indices each peer
        wants from me. Serves them from the local shard and ships features
        back; returns (P, r_max, d): row p = rows fetched from peer p."""
        served = jnp.take(table, incoming.reshape(-1), axis=0)
        served = served.reshape(incoming.shape[0], incoming.shape[1], -1)
        return jax.lax.all_to_all(served, self.axis, split_axis=0,
                                  concat_axis=0, tiled=True)

    def serve_features_batched(self, table: jnp.ndarray,
                               incoming: jnp.ndarray) -> jnp.ndarray:
        """Fold all T feature returns into ONE all_to_all.

        incoming: (T, P, r_max) server-view indices (the output of
        :meth:`exchange_indices_batched`). Returns (T, P, r_max, d):
        ``out[t, p]`` = rows fetched from peer p for step t — each
        ``out[t]`` bit-identical to the per-step :meth:`serve_features`
        slice (same gather, same exchange, only batched). With the batched
        index exchange this brings per-step mode to exactly 2 all_to_alls
        per iteration, paying a (T, P, r_max, d) staging buffer."""
        T, P, r = incoming.shape
        served = jnp.take(table, incoming.reshape(-1), axis=0)
        served = served.reshape(T, P, r, -1)
        return jax.lax.all_to_all(served, self.axis, split_axis=1,
                                  concat_axis=1, tiled=True)

    def exchange(self, table: jnp.ndarray, req: jnp.ndarray) -> jnp.ndarray:
        """table: (local_rows, d); req: (P, r_max) peer-local indices.
        Returns (P, r_max, d): row p = rows fetched from peer p."""
        return self.serve_features(table, self.exchange_indices(req))

    def grad_mean(self, grads, denom: float):
        return jax.tree.map(lambda g: jax.lax.psum(g, self.axis) / denom, grads)

    def mean_scalar(self, x):
        return jax.lax.pmean(x, self.axis)

    # -- membership hooks (repro.membership). On a real deployment the RPC
    # layer reports per-peer liveness; here a peer's death is registered
    # process-wide so every comm boundary sees the same world view.
    @staticmethod
    def kill(shard: int) -> None:
        kill_peer(shard)

    @staticmethod
    def revive(shard: int) -> None:
        revive_peer(shard)


class EmulatedComm:
    """Single-device emulation over globally-stacked arrays (leading N axis).

    ``exchange``/``grad_mean`` consume the stacked views; numerics match
    ShardComm exactly (pure data movement, no arithmetic reordering except
    the gradient sum, which is reduced in the same order)."""

    def exchange_global(self, table_g: jnp.ndarray, req_g: jnp.ndarray
                        ) -> jnp.ndarray:
        """table_g: (N, local_rows, d); req_g: (N, P, r_max).
        Returns (N, P, r_max, d): out[s, p] = table_g[p][req_g[s, p]]."""
        def per_peer(table_p, req_sp):   # (rows,d), (N,r_max)
            return jnp.take(table_p, req_sp, axis=0)          # (N, r_max, d)
        out = jax.vmap(per_peer, in_axes=(0, 1), out_axes=1)(table_g, req_g)
        return out

    def exchange_indices_batched_global(self, step_req_g: jnp.ndarray
                                        ) -> jnp.ndarray:
        """Emulated analogue of ShardComm.exchange_indices_batched.
        step_req_g: (N, T, P, r_max). Returns (N, T, P, r_max) in the
        *server* view: out[m, t, p] = step_req_g[p, t, m] — the indices
        peer p wants from shard m at step t. A pure transpose: on one
        device the index exchange is data movement only."""
        return jnp.transpose(step_req_g, (2, 1, 0, 3))

    def serve_step_global(self, table_g: jnp.ndarray, incoming_g: jnp.ndarray,
                          t, shard: int) -> jnp.ndarray:
        """Feature return for requesting ``shard`` at step ``t``.
        incoming_g: (N, T, P, r_max) server-view indices (see above).
        Returns (P, r_max, d): row p = table_g[p][incoming_g[p, t, shard]]
        — bit-identical to the per-step exchange_global slice."""
        idx = incoming_g[:, t, shard]                         # (P, r_max)
        def per_peer(table_p, idx_p):                         # (rows,d), (r,)
            return jnp.take(table_p, idx_p, axis=0)
        return jax.vmap(per_peer)(table_g, idx)               # (P, r_max, d)

    def serve_features_batched_global(self, table_g: jnp.ndarray,
                                      incoming_g: jnp.ndarray) -> jnp.ndarray:
        """Emulated analogue of ShardComm.serve_features_batched: all T
        feature returns for all shards at once. incoming_g: (N, T, P, r_max)
        server-view. Returns (N, T, P, r_max, d):
        ``out[s, t, p] = table_g[p][incoming_g[p, t, s]]`` — each [s, t]
        slice bit-identical to :meth:`serve_step_global`."""
        def per_peer(table_p, idx_p):      # (rows, d), (T, S, r)
            return jnp.take(table_p, idx_p, axis=0)           # (T, S, r, d)
        out = jax.vmap(per_peer)(table_g, incoming_g)         # (P, T, S, r, d)
        return jnp.transpose(out, (2, 1, 0, 3, 4))            # (S, T, P, r, d)

    def grad_mean_global(self, grads_g, denom: float):
        return jax.tree.map(lambda g: jnp.sum(g, axis=0) / denom, grads_g)

    # -- membership hooks: identical semantics to ShardComm's (the single
    # process stands in for the whole fabric, so both backends share the
    # module-level dead-peer registry).
    @staticmethod
    def kill(shard: int) -> None:
        kill_peer(shard)

    @staticmethod
    def revive(shard: int) -> None:
        revive_peer(shard)


# ---------------------------------------------------------------------------
# Per-shard iteration body (comm-free inner compute)
# ---------------------------------------------------------------------------

def _shard_grads(params, cfg: GNNConfig, workspace_fn: Callable,
                 hop_idx, labels, weights):
    """Scan the time steps of one shard, accumulating grads and loss.

    workspace_fn(t) -> (rows, d) feature workspace for step t (constant
    across steps in pregather mode). The per-hop feature gather is the
    Pallas ``gather_rows`` kernel on TPU (kernels/gather_agg.py) and
    ``jnp.take`` on CPU — dispatched by kernels.ops."""
    from repro.kernels import ops
    T = labels.shape[0]

    def loss_fn(p, ws, idxs, lab, w):
        feats = [ops.gather_rows(ws, i) for i in idxs]
        loss, logits = gnn_loss(p, cfg, feats, lab, weight=w)
        return loss, logits

    def step(carry, t):
        gacc, lacc = carry
        ws = workspace_fn(t)
        idxs = [h[t] for h in hop_idx]
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, ws, idxs, labels[t], weights[t])
        return (tree_add(gacc, g), lacc + loss), None

    init = (tree_zeros_like(params), jnp.zeros(()))
    (grads, loss_sum), _ = jax.lax.scan(step, init, jnp.arange(T))
    return grads, loss_sum


def _iteration_shard(params, table, cache, dev, cfg: GNNConfig,
                     pregather: bool, fold_returns: bool, denom,
                     comm: ShardComm):
    """Body run on every shard inside shard_map. ``dev`` = plan.device_args()
    with the leading shard axis already stripped. ``cache`` is the shard's
    (c_max, d) resident remote-feature rows (c_max = 0 when caching is off);
    the workspace is assembled as [local | cached | fetched], matching the
    planner's slot layout. ``denom`` is the true global batch size as a
    traced scalar (not static — see module doc)."""
    base = jnp.concatenate([table, cache], 0)     # [local | cached]
    d = table.shape[1]
    if pregather:
        recv = comm.exchange(table, dev["req"])            # (P, r_max, d)
        ws = jnp.concatenate([base, recv.reshape(-1, d)], 0)
        workspace_fn = lambda t: ws
    else:
        # All T index requests ship in one batched all_to_all before the
        # time-step scan; the scan body then only pays the feature-return
        # collective — T+1 all_to_alls per iteration instead of 2T. With
        # fold_returns the T returns also collapse into one pre-scan
        # collective: exactly 2 all_to_alls per iteration.
        incoming = comm.exchange_indices_batched(dev["step_req"])
        if fold_returns:
            recv_all = comm.serve_features_batched(table, incoming)
            def workspace_fn(t):
                return jnp.concatenate(
                    [base, recv_all[t].reshape(-1, d)], 0)
        else:
            def workspace_fn(t):
                recv = comm.serve_features(table, incoming[t])
                return jnp.concatenate([base, recv.reshape(-1, d)], 0)
    grads, loss_sum = _shard_grads(params, cfg, workspace_fn,
                                   dev["hop_idx"], dev["labels"], dev["weights"])
    grads = comm.grad_mean(grads, denom)
    loss = jax.lax.psum(loss_sum, comm.axis) / denom
    return grads, loss


# ---------------------------------------------------------------------------
# Compiled-fn cache + trace log (compile-once contract)
# ---------------------------------------------------------------------------

# (cfg, pregather, fold_returns, mesh, axis) -> jitted callable. jit's own
# cache then keys on argument shapes/dtypes, so one entry serves every shape
# bucket; a new bucket retraces exactly once and is recorded in the trace log.
_COMPILE_CACHE: dict = {}

# Fold the T per-step feature returns into one batched all_to_all when
# T·r_max is at most this many rows per peer (the staging buffer is
# (T, P, r_max, d) — the flag bounds its footprint). run_iteration's
# fold_returns=None consults this; pass an explicit bool to override.
FOLD_RETURNS_MAX_TR = 1 << 15

# Every jit (re)trace of an iteration body appends one record here. The
# append runs at *trace* time only, so executions of an already-compiled
# shape are invisible — exactly the signal the compile-once tests need.
_TRACE_LOG: list = []


def trace_count() -> int:
    """Number of iteration-body jit traces since process start / last reset."""
    return len(_TRACE_LOG)


def trace_log() -> tuple:
    """Immutable view of the trace records: (kind, model, pregather, shapes)."""
    return tuple(_TRACE_LOG)


def reset_trace_log() -> None:
    _TRACE_LOG.clear()


def clear_compile_cache() -> None:
    """Drop cached jitted callables (forces fresh traces — test isolation)."""
    _COMPILE_CACHE.clear()


def _shape_sig(tree) -> tuple:
    return tuple((tuple(x.shape), str(x.dtype)) for x in jax.tree.leaves(tree))


def _note_trace(kind: str, cfg: GNNConfig, pregather: bool, table, cache,
                dev):
    _TRACE_LOG.append((kind, cfg.model, bool(pregather),
                       tuple(table.shape), tuple(cache.shape),
                       _shape_sig(dev)))
    # telemetry (repro.obs): retraces after epoch 0 are defects the CI
    # gates watch for — surface them on the unified registry + timeline
    _obs_metrics.inc("engine.traces")
    _obs_trace.event("engine.retrace", kind=kind, model=cfg.model)


def get_compiled_iteration(cfg: GNNConfig, pregather: bool,
                           mesh: Optional[Mesh] = None, axis: str = "data",
                           fold_returns: bool = False,
                           streamed: bool = False):
    """Return the cached jitted iteration fn for this engine configuration.

    The callable's signature is ``fn(params, table, cache, dev, denom)``
    where ``cache`` is the (N, c_max, d) resident remote-feature table
    (c_max = 0 disables caching) and ``denom`` is the true global batch
    size as a float32 scalar. Building the callable is cheap; *tracing*
    happens lazily per argument-shape bucket inside jit and is what the
    trace log records. ``fold_returns`` only affects per-step mode.

    ``streamed`` (repro.features): the plan carries its own feature blocks
    (``feat_local``/``feat_fetch`` in ``dev``) gathered host-side through a
    tiered FeatureStore; ``table`` is the shared zero-width placeholder and
    NO feature collectives run — only the gradient reduction remains.
    """
    key = (cfg, bool(pregather), bool(fold_returns), mesh,
           axis if mesh is not None else None, bool(streamed))
    fn = _COMPILE_CACHE.get(key)
    if fn is None:
        fn = (_build_emulated(cfg, pregather, fold_returns, streamed)
              if mesh is None
              else _build_sharded(cfg, pregather, fold_returns, mesh, axis,
                                  streamed))
        _COMPILE_CACHE[key] = fn
    return fn


def get_compiled_inference(cfg: GNNConfig):
    """Cached jitted serving forward (repro.serve's device program).

    Signature ``fn(params, cache_tab, fetched, *hop_idx) -> logits`` where
    ``cache_tab`` is the serve cache's resident ``(c_max, d)`` hot rows
    (height 0 disables it), ``fetched`` the micro-batch's host-gathered
    ``(u_max, d)`` unique rows, and ``hop_idx[h]`` the
    ``(batch_pad · fanout^h,)`` int32 tree positions into the concatenated
    ``[cached | fetched]`` workspace. Lives in the same compile cache and
    trace log as the training programs (kind ``"infer"``), so the serving
    zero-retraces-after-warmup gate reads the exact signal the training
    compile-once tests do.
    """
    key = ("infer", cfg)
    fn = _COMPILE_CACHE.get(key)
    if fn is None:
        from repro.kernels import ops

        def infer(params, cache_tab, fetched, *hop_idx):
            _note_trace("infer", cfg, True, fetched, cache_tab,
                        list(hop_idx))
            ws = jnp.concatenate([cache_tab, fetched], 0)
            feats = [ops.gather_rows(ws, i) for i in hop_idx]
            return gnn_forward(params, cfg, feats)

        fn = jax.jit(infer)
        _COMPILE_CACHE[key] = fn
    return fn


def infer_trace_count() -> int:
    """Traces of the serving forward alone (kind ``"infer"`` records)."""
    return sum(1 for r in _TRACE_LOG if r[0] == "infer")


def optimizer_cache_key(optimizer) -> tuple:
    """Stable compile-cache identity for an optimizer: its declared value
    ``key`` when it has one (two ``adam(5e-3)`` instances then share one
    compiled program), else the instance id — safe because the cached
    callable closes over the optimizer and keeps it alive, so the id can
    never be recycled while the entry exists. Flip side: an id-keyed entry
    (schedule lr without an explicit ``key=``) pins its compiled program
    for the process lifetime — long-running sweeps over many schedule
    optimizers should pass ``key=`` (see repro.optim.adamw)."""
    key = getattr(optimizer, "key", None)
    return key if key is not None else ("optimizer-id", id(optimizer))


def get_compiled_train_step(cfg: GNNConfig, pregather: bool, optimizer,
                            mesh: Optional[Mesh] = None, axis: str = "data",
                            fold_returns: bool = False,
                            stacked: bool = False,
                            streamed: bool = False):
    """Cached *fused* train step: iteration + optimizer update, one program.

    Signature ``fn(params, opt_state, table, cache, dev, denom) ->
    (params', opt_state', loss)`` with ``params``/``opt_state`` **donated**
    (the input buffers are consumed — thread the outputs forward, never
    reuse the inputs). With ``stacked=True`` the signature takes a K-stacked
    device-arg tree and a ``(K,)`` denom vector and ``lax.scan``s the fused
    step over the K iterations, returning ``(K,)`` losses — one dispatch
    for K iterations. jit's shape cache keys on K, so different stack
    widths coexist without rebuilding."""
    key = ("fused", cfg, bool(pregather), bool(fold_returns), mesh,
           axis if mesh is not None else None, optimizer_cache_key(optimizer),
           bool(stacked), bool(streamed))
    fn = _COMPILE_CACHE.get(key)
    if fn is None:
        fn = _build_fused(cfg, pregather, fold_returns, mesh, axis,
                          optimizer, stacked, streamed)
        _COMPILE_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def resolve_fold_returns(plan, fold_returns: Optional[bool] = None) -> bool:
    """Auto-fold policy: fold the per-step feature returns when the staging
    buffer is small enough (T·r_max ≤ FOLD_RETURNS_MAX_TR). Explicit bools
    pass through; pregather mode never folds (nothing to fold)."""
    if plan.pregather:
        return False
    if fold_returns is not None:
        return bool(fold_returns)
    return plan.num_steps * plan.r_max <= FOLD_RETURNS_MAX_TR


def shard_sharding(mesh: Optional[Mesh], axis: str = "data"):
    """Placement of an array whose leading axis is the shard axis (feature
    table, cache table, plan device args): under a mesh each device holds
    its own shard; without one, ``None`` (the default device)."""
    return None if mesh is None else NamedSharding(mesh, P(axis))


def _as_device(x, sharding=None):
    """Upload only host-side leaves (to ``sharding`` when given):
    device-resident arrays pass through untouched (no per-leaf re-wrap on
    the hot path)."""
    if isinstance(x, jax.Array):
        return x
    return jnp.asarray(x) if sharding is None else jax.device_put(x, sharding)


# Host comm boundary hook (repro.resilience). In a multi-host deployment
# each all_to_all is an RPC fan-out that can stall or drop; in this harness
# the host-side point where an iteration's exchanges are initiated is the
# dispatch that stages their arguments. A fault/robustness layer installs a
# callable here; it runs BEFORE any compiled program is invoked (and thus
# before any params/opt_state buffer donation), so a raise from the hook is
# always safe to retry. None (the default) costs one global read.
_COMM_FAULT_HOOK: Optional[Callable] = None


def set_comm_fault_hook(hook: Optional[Callable]) -> None:
    """Install/remove the host comm-boundary hook (``hook(plan)``)."""
    global _COMM_FAULT_HOOK
    _COMM_FAULT_HOOK = hook


# Dead-peer registry (repro.membership). On a real multi-host deployment
# liveness comes from the RPC layer (a peer's channel errors out); in this
# single-process harness a death is registered here — by the `peer_death`
# fault kind, a membership test, or a comm backend's .kill() hook — and
# every subsequent dispatch that would contact the fabric raises
# PeerDeadError from the host staging boundary. The raise is pre-donation
# (safe to retry) and persistent (the peer stays dead until revive_peer),
# so a guarded caller's retries exhaust into the detector's CommTimeout
# with the peer attributed — exactly the signal repro.membership consumes.
_DEAD_PEERS: set = set()


class PeerDeadError(RuntimeError):
    """An exchange addressed a peer registered as dead.

    Typed transient for the retry guard (repro.resilience.comm retries it
    alongside TransientCommError): the *probe* decides permanence, not the
    raise — a flapping peer that comes back mid-retry is absorbed with no
    membership change."""

    def __init__(self, msg: str, *, peer: int = -1):
        super().__init__(msg)
        self.site = "comm"
        self.peer = int(peer)


def kill_peer(shard: int) -> None:
    """Register ``shard`` as dead; every later dispatch fails until
    :func:`revive_peer`."""
    _DEAD_PEERS.add(int(shard))


def revive_peer(shard: int) -> None:
    _DEAD_PEERS.discard(int(shard))


def peer_is_dead(shard: int) -> bool:
    return int(shard) in _DEAD_PEERS


def dead_peers() -> frozenset:
    return frozenset(_DEAD_PEERS)


def comm_fault_point(plan) -> None:
    """Run the comm-boundary hook for one iteration dispatch (pre-donation).
    Called by :func:`prepare_iteration_args` and the stacked dispatch.

    The hook runs first (a scheduled ``peer_death`` fault registers the
    kill here), then the dead-peer registry is consulted: a dispatch stages
    exchanges with *every* peer, so any registered death fails the staging
    with the peer attributed."""
    hook = _COMM_FAULT_HOOK
    if hook is not None:
        hook(plan)
    if _DEAD_PEERS:
        peer = min(_DEAD_PEERS)
        ei = getattr(plan, "epoch_it", (-1, -1))
        raise PeerDeadError(
            f"peer shard {peer} is dead at (epoch {ei[0]}, it {ei[1]}); "
            "exchange fan-out cannot be staged", peer=peer)


# (num_shards, feature_dim, dtype, sharding) -> (N, 0, d) device zeros.
# Cache-off iterations all share one zero-width cache table instead of
# allocating a fresh one per call (per-iteration host overhead).
_EMPTY_CACHE: dict = {}


def empty_cache_table(num_shards: int, feature_dim: int, dtype=np.float32,
                      sharding=None):
    key = (int(num_shards), int(feature_dim), np.dtype(dtype).str, sharding)
    tab = _EMPTY_CACHE.get(key)
    if tab is None:
        tab = _as_device(np.zeros((key[0], 0, key[1]), key[2]), sharding)
        _EMPTY_CACHE[key] = tab
    return tab


def prepare_iteration_args(table_global, plan, cache=None, sharding=None):
    """Shared argument prep for :func:`run_iteration` /
    :func:`run_train_step`: validates the cache against the plan and
    returns device-ready ``(table, cache, dev, denom)``.

    Fast paths: device-resident inputs are passed through untouched; a plan
    whose device args were pre-committed by the pipeline uploader
    (``plan.committed``, see repro.train.pipeline) skips the conversion
    walk entirely — the upload already happened off the critical path.
    Host-side leaves are placed with ``sharding`` (:func:`shard_sharding`
    of the mesh the step runs on).

    Streamed plans (repro.features): no resident table exists —
    ``table_global=None`` is replaced by the shared zero-width placeholder
    (the plan's feature blocks ride in ``dev``)."""
    comm_fault_point(plan)
    if table_global is None:
        if not getattr(plan, "streamed", False):
            raise ValueError("table_global=None is only valid for streamed "
                             "plans (tiered FeatureStore)")
        fl = plan.feat_local
        table_global = empty_cache_table(plan.num_shards, fl.shape[-1],
                                         fl.dtype, sharding)
    table_global = _as_device(table_global, sharding)
    if cache is None:
        if plan.c_max:
            raise ValueError(
                f"plan was built against a cache (c_max={plan.c_max}) "
                "but no cache table was passed")
        cache = empty_cache_table(table_global.shape[0],
                                  table_global.shape[-1], table_global.dtype,
                                  sharding)
    else:
        cache = _as_device(cache, sharding)
        if int(cache.shape[1]) != int(plan.c_max):
            raise ValueError(
                f"cache table height {cache.shape[1]} != plan c_max "
                f"{plan.c_max} (stale cache?)")
    committed = getattr(plan, "committed", None)
    if committed is not None:
        dev, denom = committed["dev"], committed["denom"]
    else:
        dev = jax.tree.map(lambda x: _as_device(x, sharding),
                           plan.device_args())
        denom = jnp.asarray(float(plan.global_batch), jnp.float32)
    return table_global, cache, dev, denom


def run_iteration(params, table_global, plan, cfg: GNNConfig,
                  mesh: Optional[Mesh] = None, cache=None,
                  fold_returns: Optional[bool] = None):
    """Execute one planned iteration.

    With a ``mesh`` (data axis length == plan.num_shards): shard_map with
    real collectives. Without: single-device emulation (same numerics).
    ``cache`` is the (N, c_max, d) device-resident remote-feature table a
    cache-aware plan was built against (required iff plan.c_max > 0; its
    height must match the plan's). ``fold_returns=None`` applies the
    :data:`FOLD_RETURNS_MAX_TR` auto policy in per-step mode.
    Returns (grads, mean_loss) — optimizer application is the caller's
    (training loop / train_step fusion decide placement; see
    :func:`run_train_step` for the fused variant).

    The jitted callable comes from the module-level compile cache: repeated
    calls with plans of the same device shapes reuse one compiled program.
    """
    table_global, cache, dev, denom = prepare_iteration_args(
        table_global, plan, cache, shard_sharding(mesh))
    fn = get_compiled_iteration(cfg, plan.pregather, mesh=mesh,
                                fold_returns=resolve_fold_returns(
                                    plan, fold_returns),
                                streamed=bool(getattr(plan, "streamed",
                                                      False)))
    return fn(params, table_global, cache, dev, denom)


def run_train_step(params, opt_state, table_global, plan, cfg: GNNConfig,
                   optimizer, mesh: Optional[Mesh] = None, cache=None,
                   fold_returns: Optional[bool] = None):
    """Execute one planned iteration *and* the optimizer update as a single
    fused dispatch. Returns ``(params', opt_state', loss)``.

    Donation contract: ``params`` and ``opt_state`` buffers are donated to
    the program — the inputs are invalid after the call; always continue
    from the returned trees. The loss stays on device (no host sync); call
    ``float(loss)`` only when you actually need the value.
    """
    table_global, cache, dev, denom = prepare_iteration_args(
        table_global, plan, cache, shard_sharding(mesh))
    fn = get_compiled_train_step(cfg, plan.pregather, optimizer, mesh=mesh,
                                 fold_returns=resolve_fold_returns(
                                     plan, fold_returns),
                                 streamed=bool(getattr(plan, "streamed",
                                                       False)))
    return fn(params, opt_state, table_global, cache, dev, denom)


def make_sharded_iteration(cfg: GNNConfig, pregather: bool, mesh: Mesh,
                           axis: str = "data", fold_returns: bool = False):
    """jit-compiled shard_map iteration ``fn(params, table, cache, dev,
    denom)`` for repeated use by the train loop (cached per config)."""
    return get_compiled_iteration(cfg, pregather, mesh=mesh, axis=axis,
                                  fold_returns=fold_returns)


def _grads_callable(cfg: GNNConfig, pregather: bool, fold_returns: bool,
                    mesh: Optional[Mesh], axis: str, kind: str,
                    streamed: bool = False):
    """Unjitted ``(params, table, cache, dev, denom) -> (grads, loss)``
    callable — the shared core the plain-iteration, fused, and stacked
    builders all wrap. ``kind`` labels the trace-log records."""
    if mesh is None:
        def fn(params, table_g, cache_g, dev, denom):
            _note_trace(kind, cfg, pregather, table_g, cache_g, dev)
            if streamed:
                return _emulated_streamed_iteration(params, cache_g, dev,
                                                    denom, cfg)
            return _emulated_iteration(params, table_g, cache_g, dev, denom,
                                       cfg, pregather, fold_returns)
        return fn

    comm = ShardComm(axis)

    def body(params, table, cache, dev, denom):
        _note_trace(kind, cfg, pregather, table, cache, dev)
        # shard_map passes per-shard views with the shard axis kept (size 1)
        table = table[0]
        cache = cache[0]
        dev = jax.tree.map(lambda x: x[0], dev)
        if streamed:
            grads, loss = _streamed_shard(params, cache, dev, cfg, denom,
                                          comm)
        else:
            grads, loss = _iteration_shard(params, table, cache, dev, cfg,
                                           pregather, fold_returns, denom,
                                           comm)
        return grads, loss

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), P(axis), P(axis), P(axis), P()),
                         out_specs=(P(), P()), check_vma=False)


def _streamed_shard(params, cache, dev, cfg: GNNConfig, denom,
                    comm: ShardComm):
    """Streamed-mode shard body: the workspace is assembled entirely from
    plan-carried feature blocks — ``[local_compact | cached | fetched]`` —
    so no feature collective runs; only the gradient psum remains."""
    d = dev["feat_local"].shape[-1]
    ws = jnp.concatenate([dev["feat_local"], cache,
                          dev["feat_fetch"].reshape(-1, d)], 0)
    grads, loss_sum = _shard_grads(params, cfg, lambda t: ws,
                                   dev["hop_idx"], dev["labels"],
                                   dev["weights"])
    grads = comm.grad_mean(grads, denom)
    loss = jax.lax.psum(loss_sum, comm.axis) / denom
    return grads, loss


def _build_sharded(cfg: GNNConfig, pregather: bool, fold_returns: bool,
                   mesh: Mesh, axis: str, streamed: bool = False):
    return jax.jit(_grads_callable(cfg, pregather, fold_returns, mesh, axis,
                                   "sharded", streamed))


def _build_fused(cfg: GNNConfig, pregather: bool, fold_returns: bool,
                 mesh: Optional[Mesh], axis: str, optimizer, stacked: bool,
                 streamed: bool = False):
    """Fused iteration + optimizer update (optionally scanned over a
    K-stack of same-shape iterations), with params/opt_state donation."""
    kind = (("emulated" if mesh is None else "sharded") + "-fused"
            + ("-stacked" if stacked else ""))
    grads_fn = _grads_callable(cfg, pregather, fold_returns, mesh, axis, kind,
                               streamed)

    if not stacked:
        def step(params, opt_state, table, cache, dev, denom):
            grads, loss = grads_fn(params, table, cache, dev, denom)
            new_params, new_state = optimizer.update(grads, opt_state, params)
            return new_params, new_state, loss

        return jax.jit(step, donate_argnums=(0, 1))

    def steps(params, opt_state, table, cache, dev_stack, denoms):
        def body(carry, x):
            p, s = carry
            dev, denom = x
            grads, loss = grads_fn(p, table, cache, dev, denom)
            p2, s2 = optimizer.update(grads, s, p)
            return (p2, s2), loss

        (p, s), losses = jax.lax.scan(body, (params, opt_state),
                                      (dev_stack, denoms))
        return p, s, losses

    return jax.jit(steps, donate_argnums=(0, 1))


def collective_counts(fn, *args) -> dict:
    """Count collective *executions* in one call of ``fn(*args)``.

    Traces ``fn`` to a jaxpr and walks it recursively, multiplying any
    collective found inside a ``scan`` body by the scan trip count — so an
    all_to_all inside the time-step loop counts T times, one hoisted ahead
    of it counts once. This is the acceptance metric for the batched
    per-step exchange: unfolded per-step mode must run exactly T+1
    all_to_alls per iteration (T feature returns + 1 batched index
    exchange), folded per-step mode and pregather mode exactly 2.
    """
    closed = jax.make_jaxpr(fn)(*args)
    counts: dict = {}
    _count_collectives(closed.jaxpr, 1, counts)
    return counts


_COLLECTIVE_PRIMS = ("all_to_all", "psum", "pmean", "all_gather",
                     "reduce_scatter", "ppermute")


def _count_collectives(jaxpr, mult: int, counts: dict) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _COLLECTIVE_PRIMS:
            counts[name] = counts.get(name, 0) + mult
        sub_mult = mult * int(eqn.params["length"]) if name == "scan" else mult
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                _count_collectives(sub, sub_mult, counts)


def _subjaxprs(v):
    if isinstance(v, jex_core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jex_core.Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for w in v:
            yield from _subjaxprs(w)


def _build_emulated(cfg: GNNConfig, pregather: bool, fold_returns: bool,
                    streamed: bool = False):
    return jax.jit(_grads_callable(cfg, pregather, fold_returns, None,
                                   "data", "emulated", streamed))


def _emulated_streamed_iteration(params, cache_g, dev, denom,
                                 cfg: GNNConfig):
    """Single-device streamed emulation: per-shard workspaces come straight
    from the plan's feature blocks (no table, no exchange). Feature values
    per tree position equal the resident path's exactly — only the slot
    numbering differs — so grads/losses are bit-identical to it."""
    ecomm = EmulatedComm()
    n = dev["labels"].shape[0]
    d = dev["feat_local"].shape[-1]
    per_shard = []
    for s in range(n):
        ws = jnp.concatenate([dev["feat_local"][s], cache_g[s],
                              dev["feat_fetch"][s].reshape(-1, d)], 0)
        hop_idx = [h[s] for h in dev["hop_idx"]]
        g, l = _shard_grads(params, cfg, lambda t, ws=ws: ws, hop_idx,
                            dev["labels"][s], dev["weights"][s])
        per_shard.append((g, l))
    grads_g = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[g for g, _ in per_shard])
    grads = ecomm.grad_mean_global(grads_g, denom)
    loss = sum(l for _, l in per_shard) / denom
    return grads, loss


def _emulated_iteration(params, table_g, cache_g, dev, denom, cfg: GNNConfig,
                        pregather: bool, fold_returns: bool):
    """Single-device emulation: python-loop over shards, explicit exchange."""
    ecomm = EmulatedComm()
    n = table_g.shape[0]
    d = table_g.shape[-1]
    if pregather:
        recv_g = ecomm.exchange_global(table_g, dev["req"])   # (N,P,r,d)
    else:
        # index exchange hoisted ahead of the scan, mirroring ShardComm's
        # batched collective (here a pure transpose — same data movement)
        incoming_g = ecomm.exchange_indices_batched_global(dev["step_req"])
        if fold_returns:
            recv_all_g = ecomm.serve_features_batched_global(table_g,
                                                             incoming_g)
    per_shard = []
    for s in range(n):
        base = jnp.concatenate([table_g[s], cache_g[s]], 0)  # [local|cached]
        if pregather:
            ws = jnp.concatenate([base, recv_g[s].reshape(-1, d)], 0)
            workspace_fn = lambda t, ws=ws: ws
        elif fold_returns:
            def workspace_fn(t, s=s, base=base):
                return jnp.concatenate(
                    [base, recv_all_g[s, t].reshape(-1, d)], 0)
        else:
            def workspace_fn(t, s=s, base=base):
                recv = ecomm.serve_step_global(table_g, incoming_g, t, s)
                return jnp.concatenate([base, recv.reshape(-1, d)], 0)
        hop_idx = [h[s] for h in dev["hop_idx"]]
        g, l = _shard_grads(params, cfg, workspace_fn, hop_idx,
                            dev["labels"][s], dev["weights"][s])
        per_shard.append((g, l))
    grads_g = jax.tree.map(lambda *xs: jnp.stack(xs), *[g for g, _ in per_shard])
    grads = ecomm.grad_mean_global(grads_g, denom)
    loss = sum(l for _, l in per_shard) / denom
    return grads, loss
