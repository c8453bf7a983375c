"""Power-emergency plane cost + criticality impact (DESIGN.md §12).

Two axes, one artifact (BENCH_serve_emergency.json):

  * **Serving cost** — arrivals/s through `ShardedServePipeline` at
    1 and 4 shards with the emergency plane off vs on. The "on" runs
    interleave a full-fleet chassis power sweep (one `CapBatch` per
    chassis through `cap_to`) every few micro-batches over a
    warm-started 2x-oversubscribed cluster, so the alarm +
    apportionment kernel really fires on the serving path; the
    overhead should stay a small fraction of the serve wall.
  * **Criticality impact** — the paper's Table-4 axis: a scheduler-sim
    run at the 2x-oversubscription chassis budget reports critical vs
    non-critical throttled-seconds under criticality-aware
    apportionment against the criticality-blind baseline on the same
    trace (aware must hold the critical number strictly lower;
    asserted in the tier-1 suite, measured here).

``--smoke`` pushes one small stream per shard count (CI);
``--regress`` re-measures the 4-shard emergency-on row against the
committed baseline.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from benchmarks.common import emit, regress_gate, require_devices
from repro.core import features as F
from repro.core.placement import ClusterState, SchedulerPolicy
from repro.core.predictor import train_service
from repro.serve import (
    EmergencyConfig, PlaneBundle, ShardedServeConfig,
    ShardedServePipeline, device_state)
from repro.serve.featurizer import table_from_history
from repro.sim.telemetry import (
    arrival_batch, arrival_stamps, generate_population)

OUT_PATH = "BENCH_serve_emergency.json"

N_HISTORY = 1500
N_ARRIVALS = 2048
BLADES_PER_CHASSIS = 12
N_CHASSIS = 64
N_SERVERS = N_CHASSIS * BLADES_PER_CHASSIS
CORES_PER_SERVER = 40
BATCH_SIZE = 256
SHARD_COUNTS = (1, 4)
#: 2x oversubscription of a 12 x 310 W chassis (the paper's headline).
BUDGET_2X = BLADES_PER_CHASSIS * 310.0 / 2.0
#: chassis power sweep cadence, in micro-batches
SWEEP_EVERY = 4
#: fixed hot-fleet utilization sample for the sweeps (alarm-rich over
#: the warm-started cluster)
SWEEP_UTIL = 0.85
WARM_OCCUPANCY = 0.6


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


def _warm_state(seed: int = 0) -> ClusterState:
    """Cluster pre-committed to ~WARM_OCCUPANCY of its cores, so the
    2x-oversubscription alarm threshold is actually reachable."""
    rng = np.random.default_rng(seed)
    st = ClusterState(n_servers=N_SERVERS,
                      cores_per_server=CORES_PER_SERVER,
                      chassis_of_server=np.arange(N_SERVERS)
                      // BLADES_PER_CHASSIS,
                      n_chassis=N_CHASSIS)
    target = WARM_OCCUPANCY * N_SERVERS * CORES_PER_SERVER
    filled, srv = 0.0, 0
    while filled < target:
        cores = int(rng.choice([2, 4, 8]))
        if st.free_cores[srv % N_SERVERS] >= cores:
            st.place(srv % N_SERVERS, cores,
                     float(rng.uniform(0.3, 0.9)),
                     bool(rng.random() < 0.4))
            filled += cores
        srv += 1
    return st


def _make_pipe(svc, hist, labels, state, n_shards, batch_size,
               emergency: bool):
    cap = max(v.subscription for v in hist.vms) + 1024
    return ShardedServePipeline(
        svc, table_from_history(hist, labels, cap),
        device_state(state), cores_per_server=CORES_PER_SERVER,
        blades_per_chassis=BLADES_PER_CHASSIS,
        config=ShardedServeConfig(
            batch_size=batch_size, n_shards=n_shards,
            planes=PlaneBundle(
                emergency=EmergencyConfig.from_model(BUDGET_2X)
                if emergency else None)))


def _sweep_power(state: ClusterState) -> np.ndarray:
    """(C,) synthetic PSU readings of the warm snapshot at SWEEP_UTIL —
    power samples are exogenous telemetry in production (BMC pollers),
    so the benchmark synthesizes them once up front; the in-scan
    apportionment still reads the *live* criticality aggregates."""
    from repro.serve import chassis_rho_levels, sampled_power
    cfg = EmergencyConfig.from_model(BUDGET_2X)
    chs = np.argsort(state.chassis_of_server, kind="stable") \
        .reshape(N_CHASSIS, -1).astype(np.int32)
    rho = np.asarray(chassis_rho_levels(
        state.gamma_nuf, state.gamma_uf, chs, np))
    return np.asarray(sampled_power(
        cfg, rho, SWEEP_UTIL, np.zeros((N_CHASSIS, 2), np.int32),
        np.zeros(N_CHASSIS, bool), np))


def _push_stream(pipe, arrivals, batch_size, emergency: bool,
                 sweep_power=None) -> dict:
    """Stream the population through `submit_to` with unit-clock
    stamps; with `emergency`, interleave a full-fleet power sweep
    every SWEEP_EVERY micro-batches (stamps tucked between arrival
    ticks, so the merge stays monotone per host)."""
    n = len(arrivals.vms)
    stamps = arrival_stamps(n)
    cap_idx = np.arange(N_CHASSIS)
    sweeps = 0
    for k, lo in enumerate(range(0, n, batch_size)):
        idx = np.arange(lo, min(lo + batch_size, n))
        pipe.submit_to(0, arrival_batch(arrivals, idx), t=stamps[idx])
        if emergency and (k + 1) % SWEEP_EVERY == 0:
            t0 = float(stamps[idx][-1])
            pipe.cap_to(0, cap_idx, sweep_power,
                        t=t0 + (cap_idx + 1) * 1e-7)
            sweeps += 1
    pipe.flush()
    return {"sweeps": sweeps, "alarms": pipe.alarms}


def run(out_path: str = OUT_PATH, smoke: bool = False) -> dict:
    import jax
    require_devices(max(SHARD_COUNTS), "serve_emergency")
    hist, arrivals, labels, svc = _train(n_trees=12 if smoke else 48)
    if smoke:
        arrivals = F.Population(vms=arrivals.vms[:256])
    bs = 64 if smoke else BATCH_SIZE
    warm = _warm_state()
    sweep_power = _sweep_power(warm)
    out = {"n_servers": N_SERVERS, "n_chassis": N_CHASSIS,
           "chassis_budget_w": BUDGET_2X, "batch_size": bs,
           "n_devices": len(jax.devices()),
           "n_arrivals": len(arrivals.vms), "configs": []}
    for n_shards in SHARD_COUNTS:
        # one warm pass per variant shares the jit cache; the timed
        # passes then ALTERNATE off/on (each from a clean cluster) so
        # progressive process warm-up — allocator, XLA autotuning —
        # cancels instead of crediting whichever variant runs last;
        # best-of over the alternations (CI noise is one-sided)
        for emergency in (False, True):
            _push_stream(_make_pipe(svc, hist, labels, warm, n_shards,
                                    bs, emergency), arrivals, bs,
                         emergency, sweep_power)
        walls = {False: np.inf, True: np.inf}
        infos = {}
        for _ in range(1 if smoke else 3):
            for emergency in (False, True):
                pipe = _make_pipe(svc, hist, labels, warm, n_shards,
                                  bs, emergency)
                t0 = time.perf_counter()
                infos[emergency] = _push_stream(pipe, arrivals, bs,
                                                emergency, sweep_power)
                walls[emergency] = min(walls[emergency],
                                       time.perf_counter() - t0)
                assert pipe.served == len(arrivals.vms)
        assert infos[True]["alarms"] > 0, \
            "emergency sweeps never alarmed — dead measurement"
        for emergency in (False, True):
            wall = walls[emergency]
            row = {"n_shards": n_shards, "emergency": emergency,
                   "arrivals_per_s": len(arrivals.vms) / wall,
                   "wall_s": wall, **infos[emergency]}
            out["configs"].append(row)
            emit(f"serve_emergency/shards{n_shards}"
                 f"/{'on' if emergency else 'off'}",
                 wall / max(len(arrivals.vms), 1) * 1e6,
                 f"arrivals_per_s={row['arrivals_per_s']:.0f} "
                 f"alarms={row['alarms']}")
    by = {(r["n_shards"], r["emergency"]): r["arrivals_per_s"]
          for r in out["configs"]}
    out["emergency_overhead_frac"] = {
        f"shards{s}": 1.0 - by[(s, True)] / by[(s, False)]
        for s in SHARD_COUNTS}

    # Table-4 axis: critical vs non-critical throttled-seconds at 2x
    from repro.sim.scheduler_sim import (PredictionChannel, SimSpec,
                                         simulate)
    sim_kw = dict(days=0.1 if smoke else 0.55, seed=0,
                  deployments_per_hour=16.0, prefill_core_ratio=0.75)
    throttled = {}
    for name, blind in (("aware", False), ("blind", True)):
        m = simulate(SchedulerPolicy(alpha=0.8),
                     PredictionChannel("ml"),
                     SimSpec(emergency=EmergencyConfig.from_model(
                         BUDGET_2X, dwell_s=1800.0,
                         criticality_blind=blind), **sim_kw))
        throttled[name] = {"uf_throttled_s": m.uf_throttled_s,
                           "nuf_throttled_s": m.nuf_throttled_s,
                           "alarms": m.alarms,
                           "migrations": m.migrations}
        emit(f"serve_emergency/table4/{name}", 0.0,
             f"uf_s={m.uf_throttled_s:.0f} "
             f"nuf_s={m.nuf_throttled_s:.0f} alarms={m.alarms}")
    out["throttled_2x"] = throttled
    if not smoke:
        with open(out_path, "w") as f:
            json.dump(out, f, indent=2)
    return out


def regress(baseline: dict) -> list:
    """Benchmark-regression gate (``benchmarks.run --regress``):
    re-measure the 4-shard emergency-on row quickly and fail on a >30%
    arrivals/s drop vs the committed BENCH_serve_emergency.json."""
    require_devices(max(SHARD_COUNTS), "serve_emergency")
    want = next(r for r in baseline["configs"]
                if r["n_shards"] == 4 and r["emergency"])
    hist, arrivals, labels, svc = _train(n_trees=48)
    arrivals = F.Population(vms=arrivals.vms[:768])
    warm = _warm_state()
    sweep_power = _sweep_power(warm)
    bs = baseline["batch_size"]
    _push_stream(_make_pipe(svc, hist, labels, warm, 4, bs, True),
                 arrivals, bs, True, sweep_power)
    walls = []
    for _ in range(3):              # best-of: CI noise is one-sided
        pipe = _make_pipe(svc, hist, labels, warm, 4, bs, True)
        t0 = time.perf_counter()
        _push_stream(pipe, arrivals, bs, True, sweep_power)
        walls.append(time.perf_counter() - t0)
    measured = len(arrivals.vms) / min(walls)
    return regress_gate("serve_emergency/shards4/on/arrivals_per_s",
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
