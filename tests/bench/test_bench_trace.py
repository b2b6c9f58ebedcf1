"""The trace reduction against known numbers: a hand-made record, and a
small trace recorded on a v5e (kept in bench/testdata)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import tracereduce as tr  # noqa: E402

HAND = {
    "window_ns": [0, 100],
    "devices": [
        [["fusion.1", 0, 10], ["gather_rows", 10, 20], ["fusion.2", 25, 15],
         ["fusion.3", 60, 20], ["all-to-all.3", 85, 10]],
        [["fusion.1", 5, 10]]],
    "host": [["plan.wait", "MainThread", 40, 20],
             ["plan.build", "prefetch_0", 0, 90]],
}


def test_bench_trace_busy_and_ops():
    assert tr.busy_ns(HAND) == [40 + 20 + 10, 10]
    assert tr.op_ns(HAND, "gather_rows") == [20, 0]
    top = dict(tr.top_ops(HAND))
    assert top["fusion"] == pytest.approx((10 + 15 + 20 + 10) / 2 / 1e9)


def test_bench_trace_idle_gaps_by_host_span():
    # chip 0 idles [40,60) under plan.wait, [80,85) and [95,100) between
    # spans; chip 1 idles [0,5) and [15,100), 20 of it under plan.wait
    gaps = dict(tr.idle_gaps(HAND, "MainThread"))
    assert gaps == pytest.approx({"plan.wait": 20 / 1e9,
                                  "(between spans)": (10 + 70) / 2 / 1e9})


def test_bench_trace_clipped_to_window():
    rec = dict(HAND, window_ns=[20, 70])
    assert tr.busy_ns(rec) == [20 + 10, 0]
    assert tr.op_ns(rec, "gather_rows") == [10, 0]


# op names as a v5e trace gives them (HLO text, cut short)
V5E_OPS = [
    ("%while.15 = (s32[]{:T(128)}, f32[47]{0:T(128)}) while((s32[]{:T(128)}",
     "while", False),
    ("%branch_0_fun.127 = f32[128000,1,128]{2,1,0:T(1,128)} custom-call("
     "s32[128000]{0:T(1024)S(1)} %bitcast.857, f32[743842,1,128]",
     "custom-call:branch_0_fun", True),
    ("%branch_0_fun.118 = f32[13312,1,640]{2,1,0:T(1,128)S(1)} custom-call("
     "s32[13312]{0:T(1024)S(1)} %pad.191", "custom-call:branch_0_fun", True),
    ("%pad.195 = f32[743842,128]{1,0:T(8,128)} pad(f32[743842,100]{1,0:T(8,"
     "128)} %get-tuple-element.3209, f32[]{:T(128)} %constant.215..sunk.10)",
     "pad", False),
    ("%dynamic-slice_reduce_fusion.20 = s32[1280]{0:T(1024)S(1)} fusion(s32["
     "4,1280]{1,0:T(4,128)}", "dynamic-slice_reduce_fusion", False),
]


@pytest.mark.parametrize("name, family, gather", V5E_OPS)
def test_bench_trace_v5e_op_names(name, family, gather):
    import re
    from bench import cell
    assert tr.op_family(name)[0] == family
    accept = cell.load_metric("gather_rows_roofline").ACCEPT
    assert bool(re.search(accept, name)) is gather


def test_bench_trace_top_ops_leave_out_containers():
    rec = {"window_ns": [0, 100], "host": [],
           "devices": [[[V5E_OPS[0][0], 0, 100], [V5E_OPS[1][0], 10, 40],
                        [V5E_OPS[3][0], 60, 20]]]}
    assert tr.top_ops(rec) == [["custom-call:branch_0_fun", 40 / 1e9],
                               ["pad", 20 / 1e9]]
