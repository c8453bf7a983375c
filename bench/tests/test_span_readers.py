"""The readers of the program's depart, cap, record, fetch and queue
spans and of its outermost-span total, on synthetic span totals; each
reads nothing from a program that has no such span."""
from types import SimpleNamespace

import pytest

from bench.harness import load_module, reader_of

SPANS = {"ingest": (40, 0.010), "merge": (40, 0.030),
         "featurize": (10, 0.020), "infer": (10, 0.010),
         "place": (10, 0.030), "commit": (10, 0.050),
         "fetch": (12, 0.046), "depart": (30, 0.060), "cap": (2, 0.020),
         "emergency": (2, 0.004), "record": (10, 0.025),
         "queue": (10, 1.500), "outermost": (142, 0.255)}


def reader(name):
    return load_module(reader_of(name)).read


def ctx(spans, batches=10, seconds=0.5):
    return SimpleNamespace(win={"spans": spans, "batches": batches,
                                "seconds": seconds})


@pytest.mark.parametrize("name,value", [
    ("planes_host_ms", 8.0),          # (60 + 20) ms over 10 batches
    ("obs_host_ms", 2.5),
    ("host_syncs_per_batch", 1.2),
    ("host_untraced_ms", 24.5),       # (500 - 255) ms over 10 batches
    ("queue_wait_ms", 150.0)])        # 1.5 s over 10 queue spans
def test_reader_on_synthetic_spans(name, value):
    assert reader(name)(ctx(SPANS)) == pytest.approx(value)


@pytest.mark.parametrize("name", [
    "planes_host_ms", "obs_host_ms", "host_syncs_per_batch",
    "host_untraced_ms", "queue_wait_ms"])
def test_reader_finds_nothing_without_the_span(name):
    older = {k: SPANS[k] for k in ("ingest", "merge", "featurize",
                                   "infer", "place", "commit")}
    assert reader(name)(ctx(older)) is None
    assert reader(name)(ctx({})) is None
