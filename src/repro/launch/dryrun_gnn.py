import os
if __name__ == "__main__":
    # Script-only (see dryrun.py): never set XLA_FLAGS on plain import.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""GNN-side dry-run: lower + compile the LeapGNN shard_map iteration on the
production data mesh (256 shards single-pod / 512 two-pod).

The paper runs 4 GPU servers; this proves the SPMD engine's collectives
(request/feature all_to_all, gradient psum) partition for a pod-scale
`data` axis. Plan arrays are ShapeDtypeStruct stand-ins — no host planning
for 256 shards happens here (plans are per-iteration host work; their
device-side shapes are what the compiler needs).

    PYTHONPATH=src python -m repro.launch.dryrun_gnn [--multi-pod]
"""
import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import make_sharded_iteration
from repro.launch.dryrun import RESULTS_DIR, collective_bytes
from repro.models.gnn import GNNConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--model", default="sage")
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--fanout", type=int, default=10)
    ap.add_argument("--feature-dim", type=int, default=600)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--batch-pad", type=int, default=8)
    ap.add_argument("--local-rows", type=int, default=16384)
    ap.add_argument("--r-max", type=int, default=2048)
    args = ap.parse_args()

    n = 512 if args.multi_pod else 256
    mesh = jax.make_mesh((n,), ("data",))
    cfg = GNNConfig(model=args.model, num_layers=args.layers,
                    hidden_dim=args.hidden, feature_dim=args.feature_dim,
                    num_classes=47, fanout=args.fanout)
    T = n                      # the full rotation: one step per shard
    f = args.fanout
    bp = args.batch_pad

    # abstract params
    from repro.models.gnn import init_gnn
    params = jax.eval_shape(lambda: init_gnn(jax.random.PRNGKey(0), cfg))

    table = jax.ShapeDtypeStruct((n, args.local_rows, args.feature_dim),
                                 jnp.float32)
    # no resident remote-feature cache in the dry-run: height-0 cached region
    cache = jax.ShapeDtypeStruct((n, 0, args.feature_dim), jnp.float32)
    dev = dict(
        req=jax.ShapeDtypeStruct((n, n, args.r_max), jnp.int32),
        step_req=None,
        hop_idx=[jax.ShapeDtypeStruct((n, T, bp * f ** h), jnp.int32)
                 for h in range(args.layers + 1)],
        labels=jax.ShapeDtypeStruct((n, T, bp), jnp.int32),
        weights=jax.ShapeDtypeStruct((n, T, bp), jnp.float32),
    )

    fn = make_sharded_iteration(cfg, pregather=True, mesh=mesh)
    denom = jax.ShapeDtypeStruct((), jnp.float32)
    lowered = fn.lower(params, table, cache, dev, denom)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    rec = {
        "kind": "hopgnn_gnn_iteration",
        "mesh": f"{n}x1(data)",
        "model": args.model,
        "status": "ok",
        "memory": {k: int(getattr(mem, k, 0)) for k in
                   ("argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes")},
        "flops": float(cost.get("flops", 0.0)),
        "collectives": coll,
    }
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    out = RESULTS_DIR / f"hopgnn.{args.model}.{n}shards.json"
    out.write_text(json.dumps(rec, indent=1))
    print(f"[ok] hopgnn {args.model} iteration on {n}-shard mesh: "
          f"temp {mem.temp_size_in_bytes / 1e9:.2f} GB/dev, "
          f"collectives {coll['total_bytes'] / 1e9:.2f} GB "
          f"({coll['count_by_op']})")


if __name__ == "__main__":
    main()
