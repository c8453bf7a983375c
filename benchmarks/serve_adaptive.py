"""Closed-loop adaptive oversubscription benchmark (DESIGN.md §15).

Two axes, one artifact (``BENCH_serve_adaptive.json``):

1. **Table-4-style ratio sweep** — `sim.scheduler_sim.simulate`
   (serve backend, emergency plane live) runs the same diurnal
   arrival trace under each fixed oversubscription ratio in
   ``FIXED_RATIOS`` (the ratio scales the admission watt budget's
   dynamic span, exactly what `serve.adaptive` scales online) and
   once under the adaptive controller. The acceptance claim mirrors
   the paper's Table 4 read: the controller must sit on the
   fixed-ratio trade-off curve's good corner — **critical
   throttled-seconds no worse than the safest fixed ratio, with at
   least the admitted-VM count of every fixed ratio that is equally
   safe** — so no offline ratio choice both admits more and throttles
   critical VMs less. Asserted at measurement time, per arm.

2. **Controller overhead at 4 shards** — the `serve_emergency`
   arrival stream with a full-fleet power sweep every
   ``SWEEP_EVERY`` micro-batches (every sweep drives an adaptive
   scan; the cadence is 2x the production stream's every-4), through
   `ShardedServePipeline` with the controller off vs on. Timing uses
   the alternating best-of discipline from `benchmarks/serve_obs`
   (docs/performance.md), hardened for the short walls here: warm
   both variants once, then alternate off/on keeping the minimum
   wall over ``BEST_OF`` rounds, each wall timing
   ``STREAMS_PER_WALL`` back-to-back streams (pipes built off the
   clock) — process noise is one-sided, so alternation + best-of
   cancels it instead of crediting whichever variant runs last.
   Acceptance: **<5% arrivals/s overhead**
   (``adaptive_overhead_frac``).

``--smoke`` runs a miniature sweep + one small stream per variant
(CI, no asserts, no artifact); ``--regress`` re-measures the 4-shard
controller-on row against the committed baseline.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from benchmarks.common import emit, regress_gate, require_devices
from benchmarks.serve_emergency import (
    BLADES_PER_CHASSIS, BUDGET_2X, CORES_PER_SERVER, N_CHASSIS,
    _sweep_power, _train, _warm_state)
from repro.core import features as F
from repro.core.placement import SchedulerPolicy
from repro.core.power_model import F_MAX, idle_power
from repro.serve import (
    AdaptiveConfig, EmergencyConfig, PlaneBundle, ResourceVector,
    ShardedServeConfig, ShardedServePipeline, device_state)
from repro.serve.featurizer import table_from_history
from repro.sim.scheduler_sim import (PredictionChannel, ServeBackendSpec,
                                     SimSpec, simulate)
from repro.sim.telemetry import arrival_batch, arrival_stamps

OUT_PATH = "BENCH_serve_adaptive.json"

# --- axis 1: the ratio sweep ----------------------------------------------
#: the offline choices the controller competes against (paper Table 4)
FIXED_RATIOS = (1.0, 1.25, 1.5, 2.0)
#: per-chassis admission watt budget at ratio 1.0 — the same 2x budget
#: the emergency plane alarms on, so ratio r admits r times the
#: budget's dynamic power span
CHASSIS_BUDGET_W = BUDGET_2X
SWEEP_DAYS = 1.25
SWEEP_SEED = 0
SWEEP_DEPLOYMENTS_PER_HOUR = 32.0
SWEEP_PREFILL = 0.4
#: noise floor for the critical-throttle comparison, as a fraction of
#: the adaptive arm's total throttled-seconds (an emergency-plane tick
#: of jitter must not flip the verdict)
UF_SLACK_FRAC = 0.002

# --- axis 2: controller overhead ------------------------------------------
BATCH_SIZE = 256
N_SHARDS = 4
#: full-fleet sweep (= adaptive scan) cadence in micro-batches —
#: every 2nd batch, twice the `serve_emergency` production stream's
#: every-4 cadence, so the overhead row is still a stress reading
SWEEP_EVERY = 2
#: timing rounds per variant (min wins) and streams per timed wall —
#: sub-second single-stream walls swing past the acceptance bar on a
#: small box, so each wall times several streams back to back
BEST_OF = 5
STREAMS_PER_WALL = 2
#: acceptance bar: controller-on costs < 5% arrivals/s at 4 shards
MAX_OVERHEAD_FRAC = 0.05


def _sweep_adaptive_cfg() -> AdaptiveConfig:
    """Controller knobs for the sweep: a short window reacting at the
    32-scans/hour cadence, backing off well before the diurnal peak
    (`sim.telemetry.diurnal_util` tops out at ~0.81) and re-ratcheting
    hard once the fleet cools."""
    return AdaptiveConfig(window=8, min_history=3, hot_util=0.63,
                          step_up=0.15, step_down=0.5, ratio_max=3.0)


def _fixed_budget_w(ratio: float) -> float:
    """Admission budget whose per-chassis rho ceiling is `ratio` times
    the ratio-1.0 ceiling (`admission.rho_cap_from_budget` is affine
    in watts: only the dynamic span above idle scales)."""
    static = BLADES_PER_CHASSIS * float(idle_power(F_MAX))
    return static + ratio * (CHASSIS_BUDGET_W - static)


def _sweep_arm(budget_w: float, adaptive_cfg, smoke: bool) -> dict:
    t0 = time.perf_counter()
    m = simulate(
        SchedulerPolicy(), PredictionChannel("ml"),
        SimSpec(days=0.2 if smoke else SWEEP_DAYS, seed=SWEEP_SEED,
                deployments_per_hour=16.0 if smoke else
                SWEEP_DEPLOYMENTS_PER_HOUR,
                prefill_core_ratio=SWEEP_PREFILL,
                serve=ServeBackendSpec(
                    backend="serve",
                    admission_budget=ResourceVector(watts=budget_w)),
                emergency=EmergencyConfig.from_model(CHASSIS_BUDGET_W),
                adaptive=adaptive_cfg))
    return {"admitted": m.placements - m.failures,
            "failures": m.failures,
            "uf_throttled_s": m.uf_throttled_s,
            "nuf_throttled_s": m.nuf_throttled_s,
            "migrations": m.migrations,
            "final_ratio": m.adaptive_ratio,
            "ratchets": m.adaptive_ratchets,
            "backoffs": m.adaptive_backoffs,
            "wall_s": time.perf_counter() - t0}


def sweep(smoke: bool = False) -> dict:
    """Run every fixed-ratio arm plus the adaptive arm on the same
    trace; outside smoke, assert the Table-4 claim per arm."""
    ratios = (1.0, 2.0) if smoke else FIXED_RATIOS
    acfg = _sweep_adaptive_cfg()
    out = {"days": 0.2 if smoke else SWEEP_DAYS, "seed": SWEEP_SEED,
           "deployments_per_hour": 16.0 if smoke else
           SWEEP_DEPLOYMENTS_PER_HOUR,
           "prefill_core_ratio": SWEEP_PREFILL,
           "chassis_budget_w": CHASSIS_BUDGET_W,
           "adaptive_cfg": {
               "window": acfg.window, "min_history": acfg.min_history,
               "hot_util": acfg.hot_util, "step_up": acfg.step_up,
               "step_down": acfg.step_down,
               "ratio_max": acfg.ratio_max},
           "arms": []}
    for r in ratios:
        row = {"name": f"fixed-{r:.2f}", "ratio": r,
               **_sweep_arm(_fixed_budget_w(r), None, smoke)}
        out["arms"].append(row)
        emit(f"serve_adaptive/sweep/{row['name']}", 0.0,
             f"admitted={row['admitted']} "
             f"uf_throttled_s={row['uf_throttled_s']:.0f}")
    adp = {"name": "adaptive", "ratio": None,
           **_sweep_arm(_fixed_budget_w(1.0), acfg, smoke)}
    out["arms"].append(adp)
    emit("serve_adaptive/sweep/adaptive", 0.0,
         f"admitted={adp['admitted']} "
         f"uf_throttled_s={adp['uf_throttled_s']:.0f} "
         f"ratchets={adp['ratchets']} backoffs={adp['backoffs']}")
    fixed = [a for a in out["arms"] if a["name"] != "adaptive"]
    slack = UF_SLACK_FRAC * (adp["uf_throttled_s"]
                             + adp["nuf_throttled_s"])
    safe = [a for a in fixed
            if a["uf_throttled_s"] <= adp["uf_throttled_s"] + slack]
    best_safe = max(safe, key=lambda a: a["admitted"], default=None)
    out["uf_slack_s"] = slack
    out["best_safe_fixed"] = None if best_safe is None \
        else best_safe["name"]
    out["capacity_gain_vs_best_safe"] = None if best_safe is None \
        else adp["admitted"] / max(best_safe["admitted"], 1)
    if not smoke:
        # the Table-4 claim, per arm: the controller ties the safest
        # offline ratio on critical throttled-seconds and admits at
        # least as much as every fixed ratio that is equally safe —
        # no fixed choice is both safer-or-equal AND higher-capacity
        min_uf = min(a["uf_throttled_s"] for a in fixed)
        assert adp["uf_throttled_s"] <= min_uf + slack, \
            f"adaptive critical throttled-s {adp['uf_throttled_s']:.0f}" \
            f" exceeds the safest fixed ratio's {min_uf:.0f}"
        for a in fixed:
            assert (a["uf_throttled_s"] > adp["uf_throttled_s"] + slack
                    or a["admitted"] <= adp["admitted"]), \
                f"{a['name']} dominates adaptive: " \
                f"admitted {a['admitted']} >= {adp['admitted']} at " \
                f"uf_throttled_s {a['uf_throttled_s']:.0f}"
    return out


# --- axis 2: controller overhead at 4 shards ------------------------------


def _make_pipe(svc, hist, labels, state, batch_size,
               adaptive_on: bool):
    cap = max(v.subscription for v in hist.vms) + 1024
    return ShardedServePipeline(
        svc, table_from_history(hist, labels, cap),
        device_state(state), cores_per_server=CORES_PER_SERVER,
        blades_per_chassis=BLADES_PER_CHASSIS,
        config=ShardedServeConfig(
            batch_size=batch_size, n_shards=N_SHARDS,
            planes=PlaneBundle(
                emergency=EmergencyConfig.from_model(BUDGET_2X),
                adaptive=AdaptiveConfig(window=8, min_history=1,
                                        hot_util=0.9, step_up=0.25)
                if adaptive_on else None)))


def _stream(pipe, arrivals, batch_size, sweep_power) -> None:
    """The `serve_emergency` stream with a full-fleet power sweep
    every ``SWEEP_EVERY`` micro-batches, so each sweep costs one
    emergency scan — and, controller on, one adaptive scan — per cap
    window."""
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
    # streams back to back — the serve_obs discipline
    # (docs/performance.md), widened because sub-second walls swing
    # past the 5% bar on a loaded box
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
                if on:
                    # the controller really consumed the sweeps:
                    # every shard's ratio ratcheted off 1.0 on the
                    # stable constant-power windows
                    assert (np.asarray(pipe.adaptive_ratio)
                            > 1.0).all()
    for on in (False, True):
        wall = walls[on]
        row = {"adaptive": on,
               "arrivals_per_s": len(arrivals.vms) / wall,
               "wall_s": wall}
        out["configs"].append(row)
        emit(f"serve_adaptive/shards{N_SHARDS}"
             f"/{'on' if on else 'off'}",
             wall / max(len(arrivals.vms), 1) * 1e6,
             f"arrivals_per_s={row['arrivals_per_s']:.0f}")
    by = {r["adaptive"]: r["arrivals_per_s"] for r in out["configs"]}
    out["adaptive_overhead_frac"] = 1.0 - by[True] / by[False]
    frac = out["adaptive_overhead_frac"]
    emit("serve_adaptive/overhead_frac", 0.0, f"frac={frac:.4f}")
    if not smoke:
        assert frac < MAX_OVERHEAD_FRAC, \
            f"adaptive-controller overhead {frac:.1%} exceeds the " \
            f"{MAX_OVERHEAD_FRAC:.0%} acceptance bar at " \
            f"{N_SHARDS} shards"
    return out


def run(out_path: str = OUT_PATH, smoke: bool = False) -> dict:
    require_devices(N_SHARDS, "serve_adaptive")
    out = {"sweep": sweep(smoke), "overhead": overhead(smoke)}
    if not smoke:
        with open(out_path, "w") as f:
            json.dump(out, f, indent=2)
    return out


def regress(baseline: dict) -> list:
    """Benchmark-regression gate (``benchmarks.run --regress``):
    re-measure the 4-shard controller-on row quickly and fail on a
    >30% arrivals/s drop vs the committed BENCH_serve_adaptive.json."""
    require_devices(N_SHARDS, "serve_adaptive")
    want = next(r for r in baseline["overhead"]["configs"]
                if r["adaptive"])
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
    return regress_gate("serve_adaptive/shards4/on/arrivals_per_s",
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
