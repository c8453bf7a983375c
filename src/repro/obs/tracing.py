"""Span tracing for the serve pipeline.

Every host phase of the serve path runs under a `Span`: ``ingest``,
``merge``, ``featurize``, ``infer``, ``place``, ``commit``, ``depart``
(a departure run's cap flush and host-side gathering, and in front of
a batch's placement the gathered removal, one ``remove`` per dispatch
nested in it), ``cap`` (the power planes' dispatches, with
``emergency`` nested per sample window),
``record`` (the observability pillars' per-batch bookkeeping) and
``fetch`` (every host read of a device array), plus ``migrate`` in the
simulator. A span records its name, start, duration, its parent (the
enclosing open span, -1 for none) and the micro-batch it served (the
pipeline's batch sequence number, -1 for a push that served none) in a
bounded ring, and its duration in the `MetricsRegistry` histogram
``serve_span_seconds{span=...}``, so long-run totals outlive the ring.

While open, a span also holds ``jax.profiler.TraceAnnotation
("serve.<name>")``: under a running profiler it lands on the trace's
host plane, on the device trace's clock and nested inside whatever
annotation the caller holds. With the profiler off the annotation is
skipped, and a span costs about 2 µs of host time (1.9 µs measured on
the host of a TPU v5e).

Durations are host wall time from `SpanTracer.clock`. Most spans time
the enqueue of device work; ``commit`` and every ``fetch`` wait for the
device. No span reads a device value itself, so tracing adds no sync
and cannot perturb a decision.

A *wait* span (`SpanTracer.record_wait`) is given its start instead of
timing a region: ``queue`` runs from the push of a micro-batch's oldest
arrival to the batch's release. It overlaps work spans, so it is left
out of the total of outermost work spans that `totals` publishes under
`OUTERMOST` (the host time the program accounts for), and it has no
profiler annotation.
"""
from __future__ import annotations

import time

import numpy as np

from .registry import MetricsRegistry

__all__ = ["OUTERMOST", "Span", "SpanTracer"]

#: `SpanTracer.totals` key of the outermost work spans' total
OUTERMOST = "outermost"
#: prefix of a span's profiler annotation
ANNOTATION_PREFIX = "serve."

_SPAN_DTYPE = np.dtype([
    ("seq", np.int64),      # span id, in order of entry
    ("parent", np.int64),   # id of the enclosing open span, -1 for none
    ("batch", np.int64),    # micro-batch served, -1 for none
    ("name", "U24"),        # span name (truncated to 24 chars)
    ("t0", np.float64),     # clock at entry
    ("dur", np.float64),    # seconds
    ("wait", bool),         # a wait span (record_wait), not work
])


class Span:
    """One timed region. Use via ``with tracer.span("place"):`` —
    entering takes an id, the enclosing span as parent and the tracer's
    current batch, and stamps the clock; exiting records the duration
    into the tracer's ring and histogram. Spans nest as `with` blocks
    do."""

    __slots__ = ("tracer", "name", "seq", "parent", "batch", "t0", "dur",
                 "_ann")

    def __init__(self, tracer: "SpanTracer", name: str):
        self.tracer = tracer
        self.name = name
        self.dur = float("nan")

    def __enter__(self) -> "Span":
        tr = self.tracer
        stack = tr._stack
        self.parent = stack[-1] if stack else -1
        self.seq = tr._next_id
        tr._next_id += 1
        self.batch = tr.batch
        stack.append(self.seq)
        self._ann = None
        if tr._annotation.is_enabled():
            self._ann = tr._annotation(ANNOTATION_PREFIX + self.name)
            self._ann.__enter__()
        self.t0 = tr.clock()
        return self

    def __exit__(self, *exc) -> None:
        tr = self.tracer
        self.dur = tr.clock() - self.t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr._stack.pop()
        tr._record(self.seq, self.parent, self.batch, self.name, self.t0,
                   self.dur, False)


class SpanTracer:
    """Bounded span recorder bound to a `MetricsRegistry`.

    The ring holds the most recent `capacity` spans (power-of-two
    sized, mask-indexed, in order of closing); every span also feeds
    ``serve_span_seconds{span=<name>}`` in the registry. `batch` is the
    micro-batch new spans are attributed to (the pipeline sets it while
    it serves one); `clock` is the host clock spans read
    (`time.perf_counter`; a test may substitute its own)."""

    def __init__(self, registry: MetricsRegistry,
                 capacity: int = 4096, clock=time.perf_counter):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self.registry = registry
        self.capacity = 1 << (capacity - 1).bit_length()
        self.clock = clock
        self.batch = -1
        self._ring: list = [None] * self.capacity
        self._next_seq = 0          # spans recorded (ring position)
        self._next_id = 0           # spans entered or waits recorded
        self._stack: list = []      # ids of the open spans
        self._hists: dict = {}      # span name -> its histogram
        self._outer = [0, 0.0]      # outermost work spans: count, s

    def __len__(self) -> int:
        return min(self._next_seq, self.capacity)

    def span(self, name: str) -> Span:
        """Context manager timing one region under `name`."""
        return Span(self, name)

    def record_wait(self, name: str, t0: float,
                    batch: int | None = None) -> None:
        """Record a wait span from `t0` (on `clock`) to now, under the
        innermost open span, for `batch` (the current batch when
        None)."""
        seq = self._next_id
        self._next_id += 1
        self._record(seq, self._stack[-1] if self._stack else -1,
                     self.batch if batch is None else batch, name, t0,
                     self.clock() - t0, True)

    def _record(self, seq, parent, batch, name, t0, dur, wait) -> None:
        self._ring[self._next_seq & (self.capacity - 1)] = (
            seq, parent, batch, name[:24], t0, dur, wait)
        self._next_seq += 1
        hist = self._hists.get(name)
        if hist is None:
            hist = self._hists[name] = self.registry.histogram(
                "serve_span_seconds",
                help="wall-clock span durations by pipeline stage",
                span=name)
        hist.observe(dur)
        if parent < 0 and not wait:
            self._outer[0] += 1
            self._outer[1] += dur

    def mark(self) -> int:
        """Ring position of the next span to close (for `claim`)."""
        return self._next_seq

    def claim(self, since: int, batch: int) -> None:
        """Attribute to `batch` the spans closed since ring position
        `since` that served no batch (the rest of a push that served
        one)."""
        mask = self.capacity - 1
        for pos in range(max(since, self._next_seq - self.capacity),
                         self._next_seq):
            row = self._ring[pos & mask]
            if row[2] < 0:
                self._ring[pos & mask] = row[:2] + (batch,) + row[3:]

    def tail(self, n: int = 64) -> np.ndarray:
        """The most recent `n` spans, oldest closed first (a copy)."""
        n = min(n, len(self))
        mask = self.capacity - 1
        return np.array([self._ring[(self._next_seq - n + i) & mask]
                         for i in range(n)], _SPAN_DTYPE)

    def totals(self) -> dict:
        """``{span name: (count, total seconds)}`` over the whole run
        (from the histograms, not just the ring), plus `OUTERMOST`: the
        count and total of the work spans that had no parent."""
        out = {name: (h.count, h.sum) for name, h in self._hists.items()}
        out[OUTERMOST] = tuple(self._outer)
        return out
