"""The reader of the program's remove spans, on synthetic span totals:
a count per batch, and nothing from a program without the span."""
from types import SimpleNamespace

from bench.harness import load_module, reader_of


def read(spans, batches=10):
    return load_module(reader_of("remove_dispatches_per_batch")).read(
        SimpleNamespace(win={"spans": spans, "batches": batches}))


def test_counts_remove_spans_per_batch():
    assert read({"depart": (40, 0.06), "remove": (12, 0.004)}) == 1.2


def test_reads_nothing_without_the_span_or_a_batch():
    assert read({"depart": (40, 0.06), "cap": (2, 0.02)}) is None
    assert read({}) is None
    assert read({"remove": (3, 0.001)}, batches=0) is None
