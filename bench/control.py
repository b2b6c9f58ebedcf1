#!/usr/bin/env python3
"""Readings that set the limits of a cell's correctness check.

    python bench/control.py --workload sage-products.train --seconds 10 \
        --seeds 1 2 3 --fault-seeds 3

For each seed, in one process: the program's run as ``bench/run.py``
makes it (first steps, warm-up, and a window sized from ``--seconds``,
timed by nothing), then the plain reference (float32, ``"highest"``) over
every iteration of it, and against that reference the numbers the check
compares (``reference.CHECKS``), with the window's readings that it does
not compare (``reference.READINGS``), for

* ``program``: the program as the benchmark runs it (the lower reading);
* ``control``: the reference with float8 (e4m3) matmul operands, the
  precision below the configuration's bfloat16 operands;

and, on the first ``--fault-seeds`` seeds, over the first three steps,
the faults

* ``half_batch``: the reference trained on half of each batch, the mean
  taken over that half;
* ``no_exchange``: the reference with every remote feature row read as
  zero, the pre-gather exchange left out.

A step that returns its state unchanged reads 1 on ``change`` by
definition and needs no run. One JSON line per seed goes to stdout. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import cell as cells  # noqa: E402
from bench import reference  # noqa: E402
from bench.features import init_params  # noqa: E402
from bench.run import (NoChip, Training, build_data,  # noqa: E402
                       check_devices, train_run)
from bench.traffic import Traffic  # noqa: E402


def readings(cell: dict, seeds, seconds: float, fault_seeds: int = 3,
             require_tpu: bool = True, say=print):
    import jax
    from repro.compile_cache import use_compile_cache
    cfg, tp = cell["config"], cell["traffic"]
    chips = int(cell["workload"]["chips"])
    check_devices(chips, require_tpu)
    use_compile_cache()
    data = build_data(cfg, say)
    workers = int(cfg["workers"])
    mesh = jax.make_mesh((workers,), ("data",)) if chips > 1 else None
    out = []
    for n, seed in enumerate(seeds):
        traffic = Traffic(tp, data.graph.train_vertices(), workers, seed)
        params0 = init_params(seed, cfg["model"])
        p0 = jax.device_get(params0)
        training = Training(cfg, tp, data, traffic, params0, mesh)
        del params0
        w, _, prog = train_run(training, cfg, tp, seconds, False)
        prog["losses"] = list(training.losses)
        prog["params_end"] = jax.device_get(training.trainer.params)
        del training
        gc.collect()
        first = w["iterations"][0]
        window = (first, first + w["iters"])
        ref = reference.Reference(cfg, data.graph, data.owner,
                                  data.local_idx, data.rows)
        batches = [(traffic.roots(g), traffic.sample_seed(g))
                   for g in range(len(prog["losses"]))]
        start = jax.device_put(p0)
        exact = reference.trajectory(ref, cfg, start, batches)
        runs = {"program": prog,
                "control": reference.trajectory(ref, cfg, start, batches,
                                                control=True)}
        first3 = batches[:reference.FIRST_STEPS]
        if n < fault_seeds:
            runs["half_batch"] = reference.trajectory(ref, cfg, start,
                                                      first3, keep=0.5)
            runs["no_exchange"] = reference.trajectory(
                ref, cfg, start, first3, drop_remote=True)
        line = {"cell": cell["name"], "seed": seed, "steps": len(batches),
                "window": list(window), "window_s": w["seconds"],
                "unplanned": w["unplanned"]}
        for k, r in runs.items():
            whole = len(r["losses"]) == len(batches)
            c = reference.compare(r, exact, p0, window if whole else None)
            line[k] = {n_: c[n_] for n_ in reference.READINGS if n_ in c}
            line[k]["leaves"] = [c["grad_leaf"], c["change_leaf"],
                                 c.get("window_change_leaf")]
        line["still_leaves"] = c["still_leaves"]
        del ref
        gc.collect()
        say(json.dumps(line))
        out.append(line)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    try:
        readings(cells.load_cell(args.workload), args.seeds, args.seconds,
                 args.fault_seeds, say=lambda s: print(s, flush=True))
    except NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
