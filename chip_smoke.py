#!/usr/bin/env python3
"""On-chip smoke test: LeapGNN training through the Trainer on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded step on a four-chip mesh

One chip: ``Trainer.fit`` on its normal path (pipelined, fused, pregather,
``strategy="hopgnn"`` with §5.3 merging) trains GraphSAGE at the paper's
§7.1 width (3 layers, hidden 128, fanout 10, 100-d features, 47 classes) on
the products-shaped graph (245k vertices, a 98 MB fp32 feature table), 4
shards emulated on the chip, 1024 roots per iteration. It fails when a loss
is not finite or the loss does not fall, when the Trainer degraded, rolled
back or replayed an epoch, when the last epoch retraced, or when the first
iteration's loss is off a plain f32 reference.

Four chips: the same configuration trained over ``jax.make_mesh((4,),
("data",))`` (real all_to_all and psum under shard_map, each device
holding its own quarter of the feature table), compared with the emulated
run on one device.

On any failure the script exits non-zero and prints no result line. On
success the last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

SHARDS = 4
BATCH = 1024               # roots per iteration, all models together
ITERS = 8
# The §5.3 controller may change the merge pattern after each of the first
# epochs (4 -> 3 -> 2 -> 1 steps, or back one step and freeze); the fifth
# epoch always runs a pattern compiled earlier, so it is held retrace-free.
EPOCHS = 5
ROOT_SEED = 0
SAMPLE_SEED = 0            # the Trainer's sample_seed_base: epoch 0, it 0
# Relative tolerance on losses and parameters. At its default precision
# the TPU multiplies f32 matrices in one bf16 pass: each operand keeps 8
# mantissa bits (relative rounding 2^-9) while products accumulate in f32.
# Emulating that rounding on a CPU (every dot operand rounded to bf16)
# moved the first-iteration loss of this model by 7.6e-5 (1024 roots) and
# 1.0e-4 (256 roots) relative to the "highest" precision reference, on the
# graph at scale 0.1; gathering wrong rows moved it by 1.7e-2 to 7.8e-2
# (permuted hop rows, zeroed neighbours, one row everywhere). 1e-3 leaves
# the rounding a tenfold margin and sits well below a wrong gather. The
# four-chip comparison of two whole trainings (emulated and sharded) is
# held to the same bound.
TOL = 1e-3


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def build_setup(scale: float = 1.0):
    from repro.graph import make_dataset
    from repro.graph.partition import community_partition, shard_features
    from repro.models.gnn import GNNConfig
    ds = make_dataset("products", scale=scale, seed=0)
    part = community_partition(ds.communities, SHARDS)
    table, owner, local_idx = shard_features(ds.features, part, SHARDS)
    cfg = GNNConfig(model="sage", num_layers=3, hidden_dim=128,
                    feature_dim=ds.feature_dim, num_classes=ds.num_classes,
                    fanout=10)
    return dict(ds=ds, part=part, table=table, owner=owner,
                local_idx=local_idx, cfg=cfg)


def roots_for(setup, batch: int):
    """Per-model roots of (epoch, it): ``batch`` distinct training
    vertices, split evenly across the models."""
    train = setup["ds"].train_vertices()

    def fn(epoch: int, it: int):
        rng = np.random.default_rng((ROOT_SEED, epoch, it))
        return np.split(rng.choice(train, batch, replace=False), SHARDS)
    return fn


def make_trainer(setup, root_fn, mesh=None):
    from repro.optim import adamw
    from repro.train import Trainer
    ds = setup["ds"]
    return Trainer(graph=ds.graph, labels=ds.labels, part=setup["part"],
                   owner=setup["owner"], local_idx=setup["local_idx"],
                   table=setup["table"], cfg=setup["cfg"],
                   optimizer=adamw(3e-3), strategy="hopgnn", pregather=True,
                   pipeline=True, mesh=mesh, root_fn=root_fn,
                   sample_seed_base=SAMPLE_SEED)


def reference_first_loss(setup, trainer, root_fn) -> float:
    """Iteration (0, 0)'s loss from a plain f32 forward at "highest"
    matmul precision: one tree block over all roots, features read by
    global id, the initial parameters. Call before ``fit`` (the fused step
    donates the parameter buffers)."""
    import jax
    import jax.numpy as jnp
    from repro.graph.sampler import sample_tree_block
    from repro.models.gnn.models import gnn_loss
    cfg, ds = setup["cfg"], setup["ds"]
    roots = np.concatenate(root_fn(0, 0))
    blk = sample_tree_block(ds.graph, roots, cfg.num_layers, cfg.fanout,
                            seed=SAMPLE_SEED)
    feats = [jnp.asarray(trainer.store.take_global(ids)) for ids in blk.hops]
    with jax.default_matmul_precision("highest"):
        loss, _ = gnn_loss(trainer.params, cfg, feats,
                           jnp.asarray(ds.labels[roots]))
    return float(loss)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling in this process
    (monitoring events), and persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event.startswith("/jax/core/compile/"):
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def fit(trainer, label: str, say):
    from repro.core import distributed as engine
    t0 = time.perf_counter()
    stats = trainer.fit(epochs=EPOCHS, iters_per_epoch=ITERS,
                        log=lambda line: say(f"{label} {line}"))
    wall = time.perf_counter() - t0
    losses = [v for st in stats for v in st.iter_losses]
    check(len(stats) == EPOCHS and len(losses) == EPOCHS * ITERS,
          f"{label}: {len(stats)} epochs, {len(losses)} losses recorded")
    check(all(math.isfinite(v) for v in losses),
          f"{label}: non-finite loss in {losses}")
    check(stats[-1].loss < stats[0].loss,
          f"{label}: loss did not fall: epoch 0 {stats[0].loss} -> "
          f"epoch {EPOCHS - 1} {stats[-1].loss}")
    check(not trainer.degradations_taken,
          f"{label}: degradation rungs taken: {trainer.degradations_taken}")
    for st in stats:
        check(st.epoch_attempts == 1 and st.rollbacks == 0,
              f"{label}: epoch {st.epoch} ran {st.epoch_attempts} attempts, "
              f"{st.rollbacks} rollbacks")
    check(stats[-1].traces == 0,
          f"{label}: last epoch retraced {stats[-1].traces}x; trace log "
          f"tail {engine.trace_log()[-stats[-1].traces:]}")
    say(f"{label} wall {wall:.3f} s for {EPOCHS}x{ITERS} iterations; "
        f"steady {1e3 * stats[-1].steady_time_s / ITERS:.3f} ms/iter "
        f"(last epoch); merge steps per epoch "
        f"{[st.num_steps for st in stats]}; traces per epoch "
        f"{[st.traces for st in stats]}")
    return stats, losses


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def one_chip(setup, say) -> None:
    root_fn = roots_for(setup, BATCH)
    trainer = make_trainer(setup, root_fn)
    ref = reference_first_loss(setup, trainer, root_fn)
    _, losses = fit(trainer, "1-chip", say)
    err = abs(losses[0] - ref) / abs(ref)
    say(f"first-iteration loss {losses[0]!r} vs f32 'highest' reference "
        f"{ref!r}: relative error {err:.3e} (tolerance {TOL})")
    check(err <= TOL, f"first-iteration loss {losses[0]} is off the "
          f"reference {ref} by {err:.3e} > {TOL}")


def four_chips(setup, say) -> None:
    import jax
    # Both trainings multiply f32 at full precision. At the default one
    # pass of bf16, a reduction-order difference of one f32 ulp can flip an
    # operand's bf16 rounding, and 40 Adam steps amplify such flips, most of
    # all in relative terms once the loss nears zero. At "highest" the two
    # runs differ by reduction order alone, so the comparison measures the
    # sharding: placement, all_to_all and psum.
    jax.config.update("jax_default_matmul_precision", "highest")
    say("both trainings at jax_default_matmul_precision=highest")
    root_fn = roots_for(setup, BATCH)
    emulated = make_trainer(setup, root_fn)
    _, losses_e = fit(emulated, "emulated (1 device)", say)
    mesh = jax.make_mesh((SHARDS,), ("data",))
    sharded = make_trainer(setup, root_fn, mesh=mesh)
    _, losses_s = fit(sharded, f"sharded ({SHARDS} devices)", say)

    local_rows, d = setup["table"].shape[1:]
    plan = sharded.build_plan(EPOCHS, 0, BATCH // SHARDS)
    placed = {"table": sharded.table, "cache": sharded._empty_table()}
    placed.update({f"plan{jax.tree_util.keystr(k)}": v for k, v in
                   jax.tree_util.tree_leaves_with_path(plan.committed["dev"])})
    for name, arr in placed.items():
        shards = arr.addressable_shards
        check(len({s.device for s in shards}) == SHARDS
              and all(s.data.shape == (1,) + arr.shape[1:] for s in shards),
              f"{name} {arr.shape} is not split 1/{SHARDS} per device: "
              f"{[(str(s.device), s.data.shape) for s in shards]}")
    say(f"table {sharded.table.shape} placed as "
        + ", ".join(f"{s.device.id}:{tuple(s.data.shape)}"
                    for s in sharded.table.addressable_shards)
        + f" (local_rows {local_rows}, d {d}); cache and "
        f"{len(placed) - 2} plan arrays split the same way")

    loss_err = max(abs(a - b) / abs(b) for a, b in zip(losses_s, losses_e))
    leaves_s = jax.tree.leaves(sharded.params)
    leaves_e = jax.tree.leaves(emulated.params)
    param_err = max(rel_err(a, b) for a, b in zip(leaves_s, leaves_e))
    say(f"sharded vs emulated: max relative loss error {loss_err:.3e} over "
        f"{len(losses_s)} iterations, max relative parameter error "
        f"{param_err:.3e} over {len(leaves_s)} leaves (tolerance {TOL})")
    check(loss_err <= TOL, f"sharded losses off the emulated run by "
          f"{loss_err:.3e}")
    check(param_err <= TOL, f"sharded parameters off the emulated run by "
          f"{param_err:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the sharded path on a four-chip mesh and "
                         "the emulated run it is compared with, nothing "
                         "else")
    args = ap.parse_args(argv)

    import jax
    from repro.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    label = f"[{dev.device_kind} x{len(devices)}]"

    def say(line: str) -> None:
        print(f"{label} {line}", flush=True)

    clock = CompileClock()
    t0 = time.perf_counter()
    setup = build_setup()
    ds = setup["ds"]
    say(f"dataset products scale 1.0: {ds.num_vertices} vertices, "
        f"{ds.graph.num_edges} edges, feature table "
        f"{ds.features.nbytes / 1e6:.1f} MB fp32, built in "
        f"{time.perf_counter() - t0:.2f} s")
    try:
        if args.chips == 1:
            one_chip(setup, say)
        else:
            four_chips(setup, say)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    say(f"compile {clock.seconds:.3f} s (trace + lower + backend), "
        f"{clock.cache_hits} persistent-cache hits, cache {cache_dir}")
    for d in devices[:args.chips]:
        say(f"device {d.id} peak_bytes_in_use "
            f"{d.memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
