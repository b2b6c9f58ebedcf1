"""The benchmark's FLOP and byte counts against hand counts."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cell as cells  # noqa: E402
from bench.reference import sample_tree  # noqa: E402

MFU = cells.load_metric("step.mfu")
ROOF = cells.load_metric("gather_rows_roofline")
TINY = {"num_layers": 2, "fanout": 2, "feature_dim": 3, "hidden_dim": 4,
        "classes": 5}


LAYERS = sorted(p.stem for p in cells.LAYERS.glob("*.py"))


# every layer file carries its hand count (HAND_COUNT) of TINY over 2, 3
# and 4 distinct vertices at hops 0-2
@pytest.mark.parametrize("layer, flops", [
    (name, cells.load_layer(name).HAND_COUNT) for name in LAYERS])
def test_bench_train_flops_hand_count(layer, flops):
    assert MFU.train_flops([2, 3, 4], dict(TINY, layer=layer)) == flops


def test_bench_flops_count_unique_vertices_per_hop():
    # 0 - 1 - 2 path: from root 0 every sample at hop 0 is 1, at hop 1 it
    # is 0 or 2; the tree has 1 + 2 + 4 rows, the message-flow graph 1 + 1
    # + at most 2 vertices
    indptr = np.array([0, 1, 3, 4])
    indices = np.array([1, 0, 2, 1], np.int32)
    hops = sample_tree(indptr, indices, np.array([0]), 2, 2, seed=5)
    assert hops[1].tolist() == [1, 1]
    assert set(hops[2].tolist()) <= {0, 2} and hops[2].size == 4
    # the same vertex at one hop always gets the same children
    assert hops[2][:2].tolist() == hops[2][2:].tolist()
    unique = [np.unique(h).size for h in hops]
    assert unique[:2] == [1, 1]


def test_bench_gather_bytes_hand_count():
    # 4 workers x 4 steps x 8 padded roots x (1 + 2 + 4) rows, 3 floats,
    # read and written
    assert ROOF.bytes_per_iteration(4, 4, 8, dict(TINY, layer="sage")) == \
        2 * (4 * 4 * 8 * 7) * 3 * 4
