"""Cross-host ingest throughput: arrivals/s vs ingest host count at a
fixed 4 shards.

Streams the same population as `benchmarks/serve_sharded` through
`ShardedServePipeline.submit_to` with 1/2/4/8 per-host queues
(`repro.serve.ingest`, docs/ingest.md): per-host stamped chunks are
pushed round-robin across hosts, micro-batches are released by the
fleet watermark as the merge allows, and the tail is flushed at end of
stream. The decisions are host-count-invariant (unique stamps); the
measurement is what the per-host queues + k-way merge *cost* on the
serving path — the merge is host-side numpy, so the overhead should
stay a small, flat fraction of the compiled serve time as hosts grow.

A separate merge-only pass (same streams, no serving) isolates the
ingest data plane in events/s. Writes BENCH_serve_ingest.json;
`--smoke` pushes one small stream per host count (CI).
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from benchmarks.common import emit, regress_gate, require_devices
from repro.core import features as F
from repro.core.placement import SchedulerPolicy
from repro.core.predictor import train_service
from repro.serve import (IngestMux, ShardedServeConfig, ShardedServePipeline)
from repro.sim.telemetry import generate_population, split_streams

OUT_PATH = "BENCH_serve_ingest.json"

N_HISTORY = 1500
N_ARRIVALS = 2048
BLADES_PER_CHASSIS = 12
N_CHASSIS = 64
N_SERVERS = N_CHASSIS * BLADES_PER_CHASSIS
CORES_PER_SERVER = 40
BATCH_SIZE = 256
N_SHARDS = 4
HOST_COUNTS = (1, 2, 4, 8)
POLICY = SchedulerPolicy()              # rank_rule — the sharded winner


def _train(seed: int = 0, n_trees: int = 48):
    pop = generate_population(N_HISTORY + N_ARRIVALS, seed=seed)
    hist = F.Population(vms=pop.vms[:N_HISTORY])
    arrivals = F.Population(vms=pop.vms[N_HISTORY:])
    labels = hist.labels.astype(np.float64)
    aggs = F.subscription_aggregates(hist, labels)
    svc = train_service(F.build_features(hist, aggs),
                        labels.astype(np.int64),
                        F.p95_bucket([v.p95_util for v in hist.vms]),
                        n_trees=n_trees, seed=seed)
    return hist, arrivals, labels, svc


def _make_pipe(svc, hist, labels, n_hosts, batch_size):
    return ShardedServePipeline.from_history(
        svc, hist, labels, n_servers=N_SERVERS,
        cores_per_server=CORES_PER_SERVER,
        blades_per_chassis=BLADES_PER_CHASSIS,
        config=ShardedServeConfig(batch_size=batch_size, policy=POLICY,
                                  n_shards=N_SHARDS,
                                  n_ingest_hosts=n_hosts))


def _push_stream(sink, streams) -> int:
    """Interleave per-host chunk pushes in global time order (the
    chunk schedule a wall clock would produce) and flush; returns the
    number of served results observed."""
    heads = [(chunks[0][0][0], h, 0) for h, chunks in enumerate(streams)
             if chunks]
    served = 0
    heads.sort()
    while heads:
        _, h, j = heads.pop(0)
        stamps, batch = streams[h][j]
        served += len(sink.submit_to(h, batch, t=stamps))
        if j + 1 < len(streams[h]):
            heads.append((streams[h][j + 1][0][0], h, j + 1))
            heads.sort()
    tail = sink.flush()
    return served + (tail is not None)


class _MergeOnly:
    """Serve-free sink: same mux traffic, no placement (isolates the
    ingest data plane)."""

    def __init__(self, n_hosts):
        self.mux = IngestMux(n_hosts)
        self.events = 0

    def submit_to(self, host, batch, t=None):
        self.mux.submit_to(host, batch, t)
        ev = self.mux.poll()
        self.events += len(ev)
        return []

    def flush(self):
        self.events += len(self.mux.drain())
        return None


def run(out_path: str = OUT_PATH, smoke: bool = False) -> dict:
    import jax
    require_devices(N_SHARDS, "serve_ingest")
    host_counts = (1, 4) if smoke else HOST_COUNTS
    hist, arrivals, labels, svc = _train(n_trees=12 if smoke else 48)
    if smoke:
        arrivals = F.Population(vms=arrivals.vms[:256])
    bs = 64 if smoke else BATCH_SIZE
    rate = 1e4                      # Poisson stamps; unique -> invariant
    out = {"n_servers": N_SERVERS, "n_shards": N_SHARDS,
           "batch_size": bs, "n_devices": len(jax.devices()),
           "n_arrivals": len(arrivals.vms), "hosts": []}
    for n_hosts in host_counts:
        chunk = max(1, bs // n_hosts)
        streams = split_streams(arrivals, n_hosts, chunk,
                                arrival_rate_per_s=rate)
        # one warm pass on a throwaway pipe (shared jit cache), then
        # the timed pass on a clean cluster
        _push_stream(_make_pipe(svc, hist, labels, n_hosts, bs),
                     streams)
        pipe = _make_pipe(svc, hist, labels, n_hosts, bs)
        t0 = time.perf_counter()
        _push_stream(pipe, streams)
        wall = time.perf_counter() - t0
        assert pipe.served == len(arrivals.vms)
        merge = _MergeOnly(n_hosts)
        t0 = time.perf_counter()
        _push_stream(merge, streams)
        merge_wall = time.perf_counter() - t0
        assert merge.events == len(arrivals.vms)
        row = {"n_hosts": n_hosts,
               "arrivals_per_s": len(arrivals.vms) / wall,
               "wall_s": wall,
               "merge_only_events_per_s":
                   merge.events / max(merge_wall, 1e-9),
               "ingest_overhead_frac": merge_wall / wall}
        out["hosts"].append(row)
        emit(f"serve_ingest/hosts{n_hosts}",
             wall / max(len(arrivals.vms), 1) * 1e6,
             f"arrivals_per_s={row['arrivals_per_s']:.0f} "
             f"merge_events_per_s="
             f"{row['merge_only_events_per_s']:.0f} "
             f"overhead={row['ingest_overhead_frac']:.3f}")
    base = out["hosts"][0]["arrivals_per_s"]
    out["throughput_vs_1host"] = {
        f"hosts{r['n_hosts']}": r["arrivals_per_s"] / base
        for r in out["hosts"]}
    if not smoke:
        with open(out_path, "w") as f:
            json.dump(out, f, indent=2)
    return out


def regress(baseline: dict) -> list:
    """Benchmark-regression gate (``benchmarks.run --regress``):
    re-measure the 4-host row quickly and fail on a >30% arrivals/s
    drop vs the committed BENCH_serve_ingest.json."""
    require_devices(N_SHARDS, "serve_ingest")
    want = next(r for r in baseline["hosts"] if r["n_hosts"] == 4)
    hist, arrivals, labels, svc = _train(n_trees=48)
    arrivals = F.Population(vms=arrivals.vms[:768])
    streams = split_streams(arrivals, 4,
                            max(1, baseline["batch_size"] // 4),
                            arrival_rate_per_s=1e4)
    _push_stream(_make_pipe(svc, hist, labels, 4,
                            baseline["batch_size"]), streams)
    walls = []
    for _ in range(3):              # best-of: CI noise is one-sided
        pipe = _make_pipe(svc, hist, labels, 4, baseline["batch_size"])
        t0 = time.perf_counter()
        _push_stream(pipe, streams)
        walls.append(time.perf_counter() - t0)
    measured = len(arrivals.vms) / min(walls)
    return regress_gate("serve_ingest/hosts4/arrivals_per_s",
                        measured, want["arrivals_per_s"])


def _main() -> int:
    if "--regress" in sys.argv:
        with open(OUT_PATH) as f:
            baseline = json.load(f)
        failures = regress(baseline)
        for msg in failures:
            print(f"REGRESS FAIL: {msg}", file=sys.stderr)
        return 1 if failures else 0
    run(smoke="--smoke" in sys.argv)
    return 0


if __name__ == "__main__":
    sys.exit(_main())
