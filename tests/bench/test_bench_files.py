"""The benchmark's data files: every cell, configuration, traffic,
metric and layer type that BENCHMARK.json names exists, parses, and
agrees with it."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cell as cells  # noqa: E402
from bench import reference  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_bench_cell_files_agree(name):
    c = cells.load_cell(name)
    entry = next(w for w in SPEC["workloads"] if w["name"] == name)
    wl = c["workload"]
    assert (wl["config"], wl["traffic"], wl["chips"], wl["why"]) == (
        entry["config"], entry["traffic"], entry["chips"], entry["why"])
    assert set(wl["limits"]) == set(reference.CHECKS)
    assert all(0 < v < 1 for v in wl["limits"].values())
    cfg = c["config"]
    assert cfg["name"] == wl["config"]
    assert cfg["workers"] >= 1
    tp = c["traffic"]
    assert tp["roots_per_iteration"] % cfg["workers"] == 0
    assert 1 <= tp["merge_steps"] <= cfg["workers"]


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_bench_config_entries(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert cfg["source"].startswith(entry["source"])
    assert entry["name"] in {w["config"] for w in SPEC["workloads"]}
    g, pub = cfg["graph"], cfg["published"]
    # published vertex count and width, nothing reduced
    assert g["vertices"] == pub["vertices"]
    assert cfg["model"]["feature_dim"] == pub["feature_dim"]
    assert entry["reduced"] == cfg["reduced"] == []
    assert cfg["workers"] == 4 and "4 workers" in cfg["deployment"]
    assert {"edges", "features", "labels"} <= set(cfg["assumed"])


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_bench_metric_readers(m):
    mod = cells.load_metric(m["name"])
    assert mod.LAYER == m["layer"]
    assert mod.MOVES == m["moves"] == "roots_per_s"
    assert mod.UNIT == m["unit"]
    assert set(m["workloads"]) <= set(CELLS)
    assert callable(mod.read)


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_bench_config_layer_has_a_file(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert (cells.LAYERS / f"{cfg['model']['layer']}.py").is_file()


@pytest.mark.parametrize("path", sorted(cells.LAYERS.glob("*.py")),
                         ids=lambda p: p.stem)
def test_bench_layer_module_exposes_its_functions(path):
    mod = cells.load_layer(path.stem)
    assert all(callable(getattr(mod, f, None))
               for f in ("init", "apply", "train_flops"))
    assert isinstance(mod.HAND_COUNT, int) and mod.HAND_COUNT > 0


def test_bench_spec_shape():
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench", "tests/bench"]
    assert [e["name"] for e in SPEC["end_to_end"]] == ["roots_per_s",
                                                        "setup_s"]
    setup = SPEC["end_to_end"][1]
    assert setup["bound"] <= 0.25
    assert all(0.01 <= e["bound"] <= 0.25 for e in SPEC["end_to_end"])
    assert 1 <= SPEC["run_seconds"] <= 51
    assert cells.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cells.peaks("cpu")


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_bench_config_records_what_the_generator_produced(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    g, made = cfg["graph"], cfg["produced"]
    assert made["vertices"] == g["vertices"]
    assert made["directed_edges"] == g["edges"] // 2 * 2
    assert 0.09 < made["train_vertices"] / g["vertices"] < 0.11
