"""Finding a cell's files by name.

    bench/workloads/<cell>.json    configuration, traffic, chips, why,
                                   limits of the correctness check,
                                   predictions
    bench/configs/<config>.json    the configuration as it is run
    bench/traffic/<traffic>.json   the traffic's parameters
    bench/metrics/<metric>.py      one per-layer metric each
    bench/layers/<layer>.py        one GNN layer type each: its initial
                                   weights, reference equations and
                                   training FLOPs

A new cell, configuration, traffic, metric or layer type is a new file;
no existing file needs an edit.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
LAYERS = BENCH / "layers"


def _load(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def load_cell(name: str) -> dict:
    """{"name", "workload", "config", "traffic"} of cell ``name``."""
    wl = _load("workloads", name)
    return {"name": name, "workload": wl,
            "config": _load("configs", wl["config"]),
            "traffic": _load("traffic", wl["traffic"])}


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def per_layer_names(cell: str, spec: dict | None = None) -> list:
    """The per-layer metrics that ``BENCHMARK.json`` asks of ``cell``."""
    spec = spec if spec is not None else benchmark()
    return [m["name"] for m in spec["per_layer"]
            if cell in m.get("workloads", [cell])]


def _module(path: Path, prefix: str, what: str):
    mod_name = prefix + "".join(c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no {what} {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str):
    """The reader module of per-layer metric ``name``."""
    return _module(BENCH / "metrics" / f"{name}.py", "bench_metric_",
                   "metric reader")


def load_layer(name: str):
    """The module of GNN layer type ``name`` (``init``, ``apply``,
    ``train_flops``), from ``LAYERS/<name>.py``."""
    return _module(LAYERS / f"{name}.py", "bench_layer_", "layer module")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table["chips"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json ({sorted(table['chips'])})")
    return table["chips"][device_kind]
