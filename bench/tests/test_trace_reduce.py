"""Busy time is the union of op intervals, gaps are named by the
harness span around them, modules and ops are summed by name."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce as T

MS = 1_000_000


def _trace():
    chip0 = {"ops": [("fusion.1", 0 * MS, 4 * MS), ("fusion.2", 2 * MS,
                                                    6 * MS),
                     ("forest_kernel", 10 * MS, 12 * MS)],
             "modules": [("jit_place_batch(7)", 0, 6 * MS),
                         ("jit_served_query(9)", 10 * MS, 12 * MS)]}
    chip1 = {"ops": [("fusion.1", 1 * MS, 3 * MS)],
             "modules": [("jit_place_batch(7)", 1 * MS, 3 * MS)]}
    spans = [(T.WINDOW_SPAN, 0, 20 * MS),
             ("bench:submit_to", 5 * MS, 15 * MS),
             ("bench:cap_to", 7 * MS, 8 * MS)]
    return {"chips": {"/device:TPU:0": chip0, "/device:TPU:1": chip1},
            "spans": spans}


def test_union_merges_and_clips():
    assert T.union([(0, 4), (2, 6), (8, 9), (9, 12)], 1, 11) == \
        [[1, 6], [8, 11]]


def test_busy_modules_ops_and_gaps():
    red = T.reduce(_trace())
    assert red["window_s"] == pytest.approx(0.020)
    # chip 0 busy 6 + 2 ms, chip 1 busy 2 ms: mean 5 ms
    assert red["busy_s"] == pytest.approx(0.005)
    assert red["modules_s"]["jit_place_batch"] == pytest.approx(0.008)
    assert T.module_seconds(red, r"place_batch") == pytest.approx(0.008)
    assert T.op_seconds(red, r"forest") == pytest.approx(0.002)
    assert T.op_seconds(red, r"nothing") is None
    gaps = dict((round(s * 1e3, 3), n) for n, s in red["idle_gaps"])
    # chip 0: 6-10 ms inside submit_to (cap_to holds 7.5? no: midpoint 8)
    assert gaps[4.0] == "bench:submit_to"
    # chip 0: 12-20 ms, midpoint 16 ms is outside every harness call
    assert gaps[8.0] == "outside harness calls"
    # chip 1: 3-20 ms, midpoint 11.5 ms inside submit_to
    assert gaps[17.0] == "bench:submit_to"
    bd = T.breakdown(red)
    assert bd["device_ops"][0][0] == "fusion.1"


def test_innermost_span_names_the_gap():
    tr = {"chips": {"/device:TPU:0": {"ops": [("a", 0, 7 * MS),
                                              ("b", 8 * MS, 20 * MS)],
                                      "modules": []}},
          "spans": [(T.WINDOW_SPAN, 0, 20 * MS),
                    ("bench:submit_to", 5 * MS, 15 * MS),
                    ("bench:cap_to", 7 * MS, 8 * MS)]}
    red = T.reduce(tr)
    assert red["idle_gaps"] == [("bench:cap_to", pytest.approx(0.001))]


def test_no_window_no_numbers():
    tr = _trace()
    tr["spans"] = tr["spans"][1:]
    assert T.reduce(tr) == {}


RECORDED = Path(__file__).parent / "data" / "trace_small.json"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace():
    rec = json.loads(RECORDED.read_text())
    tr = {"chips": {k: {kk: [tuple(e) for e in vv] for kk, vv in v.items()}
                    for k, v in rec["trace"]["chips"].items()},
          "spans": [tuple(s) for s in rec["trace"]["spans"]]}
    red = T.reduce(tr)
    for k, v in rec["expect"].items():
        assert red[k] == pytest.approx(v, rel=1e-9), k
    assert 0.0 < red["busy_s"] <= red["window_s"]
    gaps = [g for _, g in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= red["window_s"] - red["busy_s"] + 1e-9
