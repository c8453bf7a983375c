"""The readers on synthetic spans and traces, and the forest kernel's
work count against one worked by hand."""
from types import SimpleNamespace

import pytest

from bench.harness import ROOT, load_json, load_module, reader_of
from bench.roofline import forest_work, roofline_share


def reader(name):
    return load_module(reader_of(name)).read


def test_every_metric_has_a_reader():
    bench = load_json(ROOT / "BENCHMARK.json")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert reader_of(m["name"]).exists(), m["name"]


def ctx(**kw):
    base = dict(win={}, trace=None, cfg={}, peaks=None, setup_s=1.5)
    base.update(kw)
    return SimpleNamespace(**base)


def test_span_readers_per_batch():
    spans = {"ingest": (40, 0.010), "merge": (40, 0.030),
             "featurize": (10, 0.020), "infer": (10, 0.010),
             "place": (10, 0.030), "commit": (10, 0.050)}
    c = ctx(win={"spans": spans, "batches": 10})
    assert reader("ingest_host_ms")(c) == pytest.approx(4.0)
    assert reader("dispatch_host_ms")(c) == pytest.approx(6.0)
    assert reader("commit_wait_ms")(c) == pytest.approx(5.0)
    assert reader("ingest_host_ms")(ctx(win={"spans": {}, "batches": 3})) \
        is None


def test_end_to_end_readers():
    import numpy as np
    c = ctx(win={"decided": 3000, "seconds": 2.0, "paced": True,
                 "latency_s": np.linspace(0.0, 1.0, 101)})
    assert reader("decisions_per_s")(c) == 1500.0
    assert reader("decision_p95_ms")(c) == pytest.approx(950.0)
    assert reader("setup_s")(c) == 1.5
    assert reader("chassis_steps_per_s")(ctx(win={
        "chassis_steps": 216000, "seconds": 2.0})) == 108000.0


def test_trace_readers():
    trace = {"window_s": 2.0, "busy_s": 0.5, "chips": 1,
             "modules_s": {"jit_place_batch": 0.4, "jit_engine": 0.3},
             "ops_s": {"served_query.4:custom-call": 0.01},
             "batches": 100, "episodes": 3}
    c = ctx(trace=trace)
    assert reader("device_idle.serve")(c) == pytest.approx(75.0)
    assert reader("place_device_ms")(c) == pytest.approx(4.0)
    assert reader("fleet_device_ms")(c) == pytest.approx(100.0)
    assert reader("device_idle.fleet")(ctx()) is None


def test_forest_work_by_hand():
    ops, nbytes = forest_work(256, 18, 48, 6, 2, 4)
    # 4 forests x 256 rows x 48 trees x (6 compares + 2 leaf values)
    assert ops == 393_216
    # x 256*18, per forest 2*48*6 thresholds+ids, 48*64*2 leaves,
    # 256*2 outputs; 4 bytes each
    assert nbytes == 4 * (4608 + 4 * (576 + 6144 + 512))
    share, bound = roofline_share(ops, nbytes, 1e-6, 197e12, 819e9)
    assert bound == "memory"
    assert share == pytest.approx(100 * nbytes / 819e9 / 1e-6)


def test_forest_roofline_reader():
    trace = {"chips": 1, "ops_s": {"served_query.7:custom-call": 1e-3,
                                   "while.9": 1.0}, "batches": 10}
    c = ctx(trace=trace,
            cfg={"batch_size": 256, "forest": {"n_trees": 48, "depth": 6}},
            peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    _, nbytes = forest_work(256, 18, 48, 6, 2, 4)
    assert reader("forest_roofline")(c) == pytest.approx(
        100 * 10 * nbytes / 819e9 / 1e-3)
    c.trace = {"chips": 1, "ops_s": {"while.9": 1.0}, "batches": 10}
    assert reader("forest_roofline")(c) is None
