"""End-to-end LeapGNN training driver (deliverable b).

Full loop via the repro.train Trainer: synthetic dataset → METIS-style
partition → compile-once planning (shape budget + plan prefetch +
redistribution + pre-gathering + adaptive merging) → device iteration →
AdamW → eval + iteration-level checkpointing.

The Trainer plans every iteration under one quantized shape budget, so the
jitted iteration traces once per merge pattern instead of once per step:
epoch 0 pays compilation, epochs ≥1 run at steady-state device speed (both
times are printed).

Presets:
  --preset smoke   ~1 min on 1 CPU core (default)
  --preset 100m    ~100M-parameter GraphSAGE (dim 600, hidden 4096) for a
                   few hundred steps — the production-scale invocation
                   (expect hours on CPU; sized for a real accelerator).

    PYTHONPATH=src python examples/train_hopgnn.py --preset smoke
"""
import argparse

import jax

from repro.compile_cache import use_compile_cache
from repro.core import distributed as engine
from repro.graph import make_dataset
from repro.graph.partition import community_partition, shard_features
from repro.models.gnn import GNNConfig, init_gnn, model_param_bytes
from repro.optim import adamw, cosine_schedule
from repro.train import Trainer

PRESETS = {
    "smoke": dict(scale=0.03, hidden=64, fanout=4, layers=2, batch=16,
                  epochs=3, iters=8, dim=None),
    "100m": dict(scale=0.3, hidden=4096, fanout=10, layers=3, batch=256,
                 epochs=10, iters=30, dim=600),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="smoke", choices=PRESETS)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--strategy", default="hopgnn",
                    choices=["hopgnn", "model_centric", "lo"])
    ap.add_argument("--ckpt-dir", default="/tmp/hopgnn_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="per-iteration blocking loop (pre-PR5 behavior) "
                         "instead of the async fused pipeline")
    ap.add_argument("--stack", type=int, default=1,
                    help="K-stacked scan dispatch (amortizes dispatch "
                         "overhead when device iterations are tiny)")
    args = ap.parse_args()
    use_compile_cache()
    P = PRESETS[args.preset]

    ds = make_dataset("products", scale=P["scale"], seed=0,
                      feat_dim=P["dim"])
    part = community_partition(ds.communities, args.shards)
    table, owner, local_idx = shard_features(ds.features, part, args.shards)
    cfg = GNNConfig(model="sage", num_layers=P["layers"],
                    hidden_dim=P["hidden"], feature_dim=ds.feature_dim,
                    num_classes=ds.num_classes, fanout=P["fanout"])
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    print(f"dataset: {ds.num_vertices} vertices; model: "
          f"{model_param_bytes(params) / 1e6:.1f} MB params "
          f"({model_param_bytes(params) / 4 / 1e6:.1f}M)")

    total = P["epochs"] * P["iters"]
    opt = adamw(cosine_schedule(3e-3, warmup=10, total=total),
                weight_decay=1e-4, grad_clip=1.0,
                key=("cos", 3e-3, 10, total))   # value identity for the
    #             engine's fused-step compile cache (schedule isn't hashable)
    trainer = Trainer(
        graph=ds.graph, labels=ds.labels, part=part, owner=owner,
        local_idx=local_idx, table=table, cfg=cfg, optimizer=opt,
        params=params, strategy=args.strategy,
        train_vertices=ds.train_vertices(), ckpt_dir=args.ckpt_dir,
        pipeline=not args.no_pipeline, pipeline_stack=args.stack)

    tc0 = engine.trace_count()
    stats = trainer.fit(epochs=P["epochs"], iters_per_epoch=P["iters"],
                        batch_per_model=P["batch"] // args.shards,
                        eval_every=1, resume=args.resume, log=print)
    if not stats:
        print("nothing to do: checkpoint already covers every epoch "
              f"(step {trainer.global_step})")
        return
    first, rest = stats[0], stats[1:]
    if rest:
        print(f"compile-once: epoch 0 {first.time_s:.2f}s "
              f"(incl. compile) vs epochs>=1 mean "
              f"{sum(s.time_s for s in rest) / len(rest):.2f}s; "
              f"{engine.trace_count() - tc0} traces total, "
              f"budget {trainer.budget.signature()} "
              f"({trainer.budget.rebuckets} rebuckets)")
        if first.pipelined:
            print(f"pipeline: steady "
                  f"{1000 * rest[-1].steady_time_s / P['iters']:.1f} ms/iter "
                  f"(synced window), dispatch "
                  f"{1000 * rest[-1].dispatch_s / P['iters']:.1f} ms/iter, "
                  f"{trainer._uploader.uploads} committed uploads, "
                  f"{trainer._uploader.shape_changes} shape changes")
    print(f"done; checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
