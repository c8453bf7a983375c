"""All-resource oversubscription benchmark (DESIGN.md §16,
docs/resources.md).

Three axes, one artifact (``BENCH_serve_resources.json``):

1. **Joint vs power-only sweep** (Table-4-style) — the same diurnal
   arrival trace through `sim.scheduler_sim.simulate` (serve backend,
   emergency plane live) under (a) a power-only admission budget and
   (b) the joint (watts+cores+GB) budget: a wider watt ceiling whose
   risk is bounded by Coach-style cores/GB ceilings ratcheting on the
   diurnal trough (``diurnal_ratchet``) with the ballooning rung
   absorbing the residual alarms. Acceptance, asserted at measurement
   time: **joint admits strictly more VMs at equal-or-lower critical
   (UF) throttled-seconds**.

2. **Mitigation-ladder comparison** — cap -> migrate vs
   cap -> balloon -> migrate at the *same* admission budget on the
   same trace. Acceptance: the ballooned ladder performs **fewer
   migrations** and no more critical throttled-seconds (the balloon
   serves the watt deficit the NUF frequency floor cannot, so the
   migration trigger `emergency.mitigation_due` never dwells hot).

3. **Resource-plane overhead at 4 shards** — the `serve_emergency`
   arrival stream with a full-fleet power sweep every ``SWEEP_EVERY``
   micro-batches (the production every-4 cadence) through
   `ShardedServePipeline`, power-only ledger (watt-axis cluster
   budget + emergency) vs the full joint plane (3-axis budget +
   emergency + ballooning). Timing uses the alternating best-of
   discipline from `benchmarks/serve_adaptive` (docs/performance.md).
   Acceptance: **<5% arrivals/s overhead**
   (``resource_plane_overhead_frac``).

``--smoke`` runs miniature arms (CI, no asserts, no artifact);
``--regress`` re-measures the 4-shard joint-plane row against the
committed baseline.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from benchmarks.common import emit, regress_gate, require_devices
from benchmarks.serve_emergency import (
    BLADES_PER_CHASSIS, BUDGET_2X, CORES_PER_SERVER, N_CHASSIS,
    N_SERVERS, _sweep_power, _train, _warm_state)
from repro.core import features as F
from repro.core.placement import SchedulerPolicy
from repro.core.power_model import F_MAX, idle_power
from repro.serve import (
    BallooningConfig, EmergencyConfig, PlaneBundle, ResourceVector,
    ShardedServeConfig, ShardedServePipeline, device_state)
from repro.serve.featurizer import table_from_history
from repro.sim.scheduler_sim import (GB_PER_CORE, PredictionChannel,
                                     ServeBackendSpec, SimSpec,
                                     simulate)
from repro.sim.telemetry import arrival_batch, arrival_stamps

OUT_PATH = "BENCH_serve_resources.json"

# --- axis 1: joint vs power-only sweep ------------------------------------
#: sim fleet geometry (scheduler_sim constants): 60 chassis of
#: 12 x 40-core blades, GB_PER_CORE GB of DRAM per core
SIM_CHASSIS_CORES = 12 * 40
SIM_CHASSIS_GB = SIM_CHASSIS_CORES * GB_PER_CORE
SWEEP_DAYS = 0.5
SWEEP_SEED = 0
SWEEP_DEPLOYMENTS_PER_HOUR = 32.0
SWEEP_PREFILL = 0.45
#: per-chassis budgets: the emergency plane alarms at EMER_BUDGET_W;
#: the power-only arm admits up to the same watts, the joint arm
#: widens the *dynamic* watt span by JOINT_WATT_SPAN while capping
#: cores/GB at a fraction of physical capacity (the Coach restraint:
#: binding at the peak, ratcheted vacuous on the trough)
EMER_BUDGET_W = 2000.0
POWER_ONLY_W = 2000.0
JOINT_WATT_SPAN = 1.2
JOINT_CORES_FRAC = 0.85
JOINT_GB_FRAC = 0.9
#: noise floor for the critical-throttle comparison, seconds — one
#: emergency-plane tick of jitter must not flip a deterministic tie
UF_SLACK_S = 60.0

# --- axis 2: the mitigation ladder ----------------------------------------
LADDER_DAYS = 0.5
LADDER_PREFILL = 0.5
LADDER_EMER_W = BUDGET_2X            # 1860 — the paper's 2x headline
#: both ladder arms admit to the same widened watt ceiling, hot enough
#: that the migration rung actually fires without ballooning
LADDER_ADMIT_SPAN = 1.3

# --- axis 3: plane overhead at 4 shards -----------------------------------
BATCH_SIZE = 256
N_SHARDS = 4
#: full-fleet sweep cadence in micro-batches — the production
#: stream's every-4 (`benchmarks/serve_emergency`). Unlike the fused
#: power-only path, every ballooned sweep costs one *standalone*
#: sharded dispatch (the rung applies eagerly so its kernel reads the
#: live memory ledger — `pipeline._apply_caps`), so the overhead
#: scales with sweep cadence: ~10% at a 2x-stress every-2 cadence,
#: <1% here
SWEEP_EVERY = 4
BEST_OF = 5
STREAMS_PER_WALL = 2
#: acceptance bar: the joint ledger + ballooning rung cost < 5%
#: arrivals/s over the power-only ledger at 4 shards
MAX_OVERHEAD_FRAC = 0.05


def _static_w() -> float:
    return BLADES_PER_CHASSIS * float(idle_power(F_MAX))


def _widened_w(base_w: float, span: float) -> float:
    """Watt budget whose *dynamic* span above chassis idle is `span`
    times the base's (idle power is not oversubscribable)."""
    static = _static_w()
    return static + span * (base_w - static)


def _arm_metrics(m, wall_s: float) -> dict:
    return {"admitted": m.placements - m.failures,
            "failures": m.failures,
            "uf_throttled_s": m.uf_throttled_s,
            "nuf_throttled_s": m.nuf_throttled_s,
            "alarms": m.alarms, "migrations": m.migrations,
            "balloon_events": m.balloon_events,
            "balloon_reclaimed_gb": m.balloon_reclaimed_gb,
            "wall_s": wall_s}


def _sim_arm(name: str, spec: SimSpec) -> dict:
    t0 = time.perf_counter()
    m = simulate(SchedulerPolicy(), PredictionChannel("ml"), spec)
    row = {"name": name,
           **_arm_metrics(m, time.perf_counter() - t0)}
    emit(f"serve_resources/{name}", 0.0,
         f"admitted={row['admitted']} "
         f"uf_throttled_s={row['uf_throttled_s']:.0f} "
         f"migrations={row['migrations']}")
    return row


def sweep(smoke: bool = False) -> dict:
    """Power-only vs joint admission on the same diurnal trace;
    outside smoke, assert the capacity-at-equal-safety claim."""
    days = 0.1 if smoke else SWEEP_DAYS
    kw = dict(days=days, seed=SWEEP_SEED,
              deployments_per_hour=SWEEP_DEPLOYMENTS_PER_HOUR,
              prefill_core_ratio=SWEEP_PREFILL)
    ecfg = EmergencyConfig.from_model(EMER_BUDGET_W)
    joint_w = _widened_w(POWER_ONLY_W, JOINT_WATT_SPAN)
    joint_vec = ResourceVector(
        watts=joint_w, cores=JOINT_CORES_FRAC * SIM_CHASSIS_CORES,
        gb=JOINT_GB_FRAC * SIM_CHASSIS_GB)
    out = {**kw, "emergency_budget_w": EMER_BUDGET_W,
           "power_only_w": POWER_ONLY_W,
           "joint_budget": {"watts": joint_w,
                            "cores": joint_vec.cores,
                            "gb": joint_vec.gb},
           "uf_slack_s": UF_SLACK_S, "arms": []}
    power = _sim_arm("sweep/power-only", SimSpec(
        serve=ServeBackendSpec(
            backend="serve",
            admission_budget=ResourceVector(watts=POWER_ONLY_W)),
        emergency=ecfg, **kw))
    joint = _sim_arm("sweep/joint", SimSpec(
        serve=ServeBackendSpec(backend="serve",
                               admission_budget=joint_vec,
                               diurnal_ratchet=True),
        emergency=ecfg, ballooning=BallooningConfig(), **kw))
    out["arms"] = [power, joint]
    out["capacity_gain"] = joint["admitted"] / max(power["admitted"], 1)
    if not smoke:
        assert joint["admitted"] > power["admitted"], \
            f"joint admitted {joint['admitted']} <= power-only's " \
            f"{power['admitted']}"
        assert joint["uf_throttled_s"] \
            <= power["uf_throttled_s"] + UF_SLACK_S, \
            f"joint critical throttled-s {joint['uf_throttled_s']:.0f}" \
            f" exceeds power-only's {power['uf_throttled_s']:.0f}"
    return out


def ladder(smoke: bool = False) -> dict:
    """cap -> migrate vs cap -> balloon -> migrate at the same
    admission budget; outside smoke, assert the fewer-migrations
    claim."""
    days = 0.1 if smoke else LADDER_DAYS
    kw = dict(days=days, seed=SWEEP_SEED,
              deployments_per_hour=SWEEP_DEPLOYMENTS_PER_HOUR,
              prefill_core_ratio=LADDER_PREFILL)
    ecfg = EmergencyConfig.from_model(LADDER_EMER_W)
    admit = ResourceVector(
        watts=_widened_w(LADDER_EMER_W, LADDER_ADMIT_SPAN))
    out = {**kw, "emergency_budget_w": LADDER_EMER_W,
           "admission_w": admit.watts, "arms": []}
    base = _sim_arm("ladder/cap-migrate", SimSpec(
        serve=ServeBackendSpec(backend="serve",
                               admission_budget=admit),
        emergency=ecfg, **kw))
    rung = _sim_arm("ladder/cap-balloon-migrate", SimSpec(
        serve=ServeBackendSpec(backend="serve",
                               admission_budget=admit),
        emergency=ecfg, ballooning=BallooningConfig(), **kw))
    out["arms"] = [base, rung]
    if not smoke:
        assert base["migrations"] > 0, \
            "cap->migrate never migrated: the ladder comparison is vacuous"
        assert rung["migrations"] < base["migrations"], \
            f"ballooned ladder migrated {rung['migrations']}x, " \
            f"cap->migrate {base['migrations']}x"
        assert rung["uf_throttled_s"] \
            <= base["uf_throttled_s"] + UF_SLACK_S
        assert rung["balloon_events"] > 0
    return out


# --- axis 3: plane overhead at 4 shards -----------------------------------


def _make_pipe(svc, hist, labels, state, batch_size, joint_on: bool):
    cap = max(v.subscription for v in hist.vms) + 1024
    watts = N_CHASSIS * BUDGET_2X
    if joint_on:
        budget = ResourceVector(
            watts=watts,
            cores=0.9 * N_SERVERS * CORES_PER_SERVER,
            gb=0.9 * N_SERVERS * CORES_PER_SERVER * GB_PER_CORE)
    else:
        budget = ResourceVector(watts=watts)
    return ShardedServePipeline(
        svc, table_from_history(hist, labels, cap),
        device_state(state), cores_per_server=CORES_PER_SERVER,
        blades_per_chassis=BLADES_PER_CHASSIS,
        config=ShardedServeConfig(
            batch_size=batch_size, n_shards=N_SHARDS,
            planes=PlaneBundle(
                cluster_budget=budget,
                emergency=EmergencyConfig.from_model(BUDGET_2X),
                ballooning=BallooningConfig() if joint_on else None)))


def _stream(pipe, arrivals, batch_size, sweep_power) -> None:
    """The `serve_emergency` stream with a full-fleet power sweep
    every ``SWEEP_EVERY`` micro-batches, so each sweep costs one
    emergency scan — and, joint plane on, one ballooning scan — per
    cap window."""
    n = len(arrivals.vms)
    stamps = arrival_stamps(n)
    cap_idx = np.arange(N_CHASSIS)
    for bi, lo in enumerate(range(0, n, batch_size)):
        idx = np.arange(lo, min(lo + batch_size, n))
        pipe.submit_to(0, arrival_batch(arrivals, idx), t=stamps[idx])
        if (bi + 1) % SWEEP_EVERY == 0:
            t0 = float(stamps[idx][-1])
            pipe.cap_to(0, cap_idx, sweep_power,
                        t=t0 + (cap_idx + 1) * 1e-7)
    pipe.flush()


def overhead(smoke: bool = False) -> dict:
    hist, arrivals, labels, svc = _train(n_trees=12 if smoke else 48)
    if smoke:
        arrivals = F.Population(vms=arrivals.vms[:256])
    bs = 64 if smoke else BATCH_SIZE
    warm = _warm_state()
    sweep_power = _sweep_power(warm)
    out = {"n_shards": N_SHARDS, "batch_size": bs,
           "n_arrivals": len(arrivals.vms),
           "max_overhead_frac": MAX_OVERHEAD_FRAC, "configs": []}
    # warm the jit caches once per variant, then ALTERNATE off/on
    # keeping the best (minimum) wall, each wall timing several
    # streams back to back — the serve_adaptive discipline
    # (docs/performance.md): process noise is one-sided, so
    # alternation + best-of cancels it
    for on in (False, True):
        _stream(_make_pipe(svc, hist, labels, warm, bs, on),
                arrivals, bs, sweep_power)
    per = 1 if smoke else STREAMS_PER_WALL
    walls = {False: np.inf, True: np.inf}
    for _ in range(1 if smoke else BEST_OF):
        for on in (False, True):
            pipes = [_make_pipe(svc, hist, labels, warm, bs, on)
                     for _ in range(per)]
            t0 = time.perf_counter()
            for pipe in pipes:
                _stream(pipe, arrivals, bs, sweep_power)
            walls[on] = min(walls[on],
                            (time.perf_counter() - t0) / per)
            for pipe in pipes:
                assert pipe.served == len(arrivals.vms)
    for on in (False, True):
        wall = walls[on]
        row = {"joint": on,
               "arrivals_per_s": len(arrivals.vms) / wall,
               "wall_s": wall}
        out["configs"].append(row)
        emit(f"serve_resources/shards{N_SHARDS}"
             f"/{'joint' if on else 'power-only'}",
             wall / max(len(arrivals.vms), 1) * 1e6,
             f"arrivals_per_s={row['arrivals_per_s']:.0f}")
    by = {r["joint"]: r["arrivals_per_s"] for r in out["configs"]}
    out["resource_plane_overhead_frac"] = 1.0 - by[True] / by[False]
    frac = out["resource_plane_overhead_frac"]
    emit("serve_resources/overhead_frac", 0.0, f"frac={frac:.4f}")
    if not smoke:
        assert frac < MAX_OVERHEAD_FRAC, \
            f"resource-plane overhead {frac:.1%} exceeds the " \
            f"{MAX_OVERHEAD_FRAC:.0%} acceptance bar at " \
            f"{N_SHARDS} shards"
    return out


def run(out_path: str = OUT_PATH, smoke: bool = False) -> dict:
    require_devices(N_SHARDS, "serve_resources")
    out = {"sweep": sweep(smoke), "ladder": ladder(smoke),
           "overhead": overhead(smoke)}
    if not smoke:
        with open(out_path, "w") as f:
            json.dump(out, f, indent=2)
    return out


def regress(baseline: dict) -> list:
    """Benchmark-regression gate (``benchmarks.run --regress``):
    re-measure the 4-shard joint-plane row quickly and fail on a >30%
    arrivals/s drop vs the committed BENCH_serve_resources.json."""
    require_devices(N_SHARDS, "serve_resources")
    want = next(r for r in baseline["overhead"]["configs"]
                if r["joint"])
    hist, arrivals, labels, svc = _train(n_trees=48)
    arrivals = F.Population(vms=arrivals.vms[:768])
    warm = _warm_state()
    sweep_power = _sweep_power(warm)
    bs = baseline["overhead"]["batch_size"]
    _stream(_make_pipe(svc, hist, labels, warm, bs, True),
            arrivals, bs, sweep_power)
    walls = []
    for _ in range(3):              # best-of: CI noise is one-sided
        pipe = _make_pipe(svc, hist, labels, warm, bs, True)
        t0 = time.perf_counter()
        _stream(pipe, arrivals, bs, sweep_power)
        walls.append(time.perf_counter() - t0)
    measured = len(arrivals.vms) / min(walls)
    return regress_gate("serve_resources/shards4/joint/arrivals_per_s",
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
