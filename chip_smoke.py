"""Smoke run of the admission and power plane on one TPU chip.

Drives the system's main path through the entry points a user calls,
in one process, and fails (non-zero exit, nothing caught) when any
check below fails or when JAX finds no TPU:

* serve phase — the paper's Fig-7 cluster (60 chassis x 12 blades x 40
  cores). A 1,500-VM history generated from ``--seed`` trains the
  prediction service at Table III defaults (48 trees);
  `ServePipeline.from_history` serves 4,096 arrivals with the Pallas
  forest kernel and the production plane bundle (chassis budget,
  emergency, ballooning, adaptive, full observability), streamed over
  two ingest hosts with `submit_to`, interleaved with `depart_to`
  departures and `cap_to` power sweeps that raise alarms. Checks,
  layer by layer against the host: the features agree with the same
  featurizer on the CPU, the gated prediction heads agree with the
  host `PredictionService.query` on the same features, every placement
  equals the one the same pipeline makes for the same stream on the
  host CPU (DESIGN.md §9, "Across backends"), no server is over its
  cores, admitted + rejected = arrivals, and at least one alarm and one
  cap were applied;
* fleet phase — `run_fleet(backend="jax")` on the Table IV campus
  (1,440 chassis) for 150 control steps, with 8 sampled chassis
  compared against the numpy oracle within 0.5 W per step.

``--chips 4`` runs only the sharded path instead: the same stream
through `ShardedServePipeline` with four shards on a real 4-device mesh
(``use_shard_map=True``) under a cluster budget whose token pools
bind, compared with the identical config run under vmap on one device.

Earlier lines print per-phase compile seconds, wall seconds and counts
(set-up and smoke timings, not speeds). The last line is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.

    python3 chip_smoke.py                 # one chip
    python3 chip_smoke.py --chips 4       # the sharded path, four chips
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse   # tiny, on CPU

``--rehearse`` shrinks every size and accepts the CPU, so the script's
paths can be checked without a chip (with ``--chips 4``, force four
CPU devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass
from pathlib import Path

# the placement parity check serves the same stream on the host CPU in
# this process, so a platform list that names only the TPU gains it
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"
# that CPU twin must round every multiply and add on its own, as the TPU
# and numpy do: XLA:CPU contracts a*b + c into one FMA wherever the ISA
# has FMA instructions, and AVX (without AVX2) has none
os.environ["XLA_FLAGS"] = " ".join(
    [os.environ.get("XLA_FLAGS", ""), "--xla_cpu_max_isa=AVX"]).strip()
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import features as F  # noqa: E402
from repro.core.power_model import F_MAX, ServerPowerModel, idle_power  # noqa: E402
from repro.core.predictor import UF, train_service  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.obs import Observability  # noqa: E402
from repro.serve import (  # noqa: E402
    AdaptiveConfig, BallooningConfig, EmergencyConfig, PlaneBundle,
    ResourceVector, ServeConfig, ServePipeline, ShardedServeConfig,
    ShardedServePipeline, featurize_batch, pack_service, served_query)
from repro.sim.chassis_sim import paper_chassis_specs  # noqa: E402
from repro.sim.fleet import build_layout, run_fleet  # noqa: E402
from repro.sim.telemetry import (  # noqa: E402
    arrival_batch, generate_population, split_streams)


@dataclass(frozen=True)
class Sizes:
    n_history: int = 1500
    n_arrivals: int = 4096
    n_chassis: int = 60              # Fig 7: 60 chassis x 12 blades
    blades: int = 12
    cores: int = 40
    batch: int = 256
    n_trees: int = 48                # Table III
    fleet_chassis: int = 1440        # Table IV campus
    fleet_steps: int = 150
    fleet_sampled: int = 8


#: every size cut down for a CPU rehearsal of the script's paths
REHEARSAL = Sizes(n_history=400, n_arrivals=512, n_chassis=4, batch=64,
                  n_trees=12, fleet_chassis=16, fleet_steps=20,
                  fleet_sampled=4)

N_HOSTS = 2
#: the paper's 2x oversubscription of a 12 x 310 W chassis
CHASSIS_BUDGET_W = 12 * 310.0 / 2.0
#: a departure batch of the oldest live VMs every DEPART_EVERY pushes
DEPART_EVERY, DEPART_N = 4, 32
#: a full-fleet power sweep every CAP_EVERY pushes
CAP_EVERY = 3
#: --chips 4: the cluster token pool as a fraction of the summed
#: chassis allowances, so the pools bind before the chassis caps do
CLUSTER_POOL_FRAC = 0.6
#: host oracle and chip agree on confidences this closely; a gated head
#: may differ only where the host confidence sits this close to the gate
CONF_ATOL = 1e-5
FLEET_BUDGET_W = (2300.0, 2600.0)
POWER_TOL_W = 0.5


class CompileClock:
    """Sums the seconds JAX spends tracing, lowering and compiling."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def make_world(sz: Sizes, seed: int):
    pop = generate_population(sz.n_history + sz.n_arrivals, seed=seed)
    hist = F.Population(vms=pop.vms[:sz.n_history])
    arrivals = F.Population(vms=pop.vms[sz.n_history:])
    labels = hist.labels.astype(np.float64)
    aggs = F.subscription_aggregates(hist, labels)
    svc = train_service(F.build_features(hist, aggs),
                        labels.astype(np.int64),
                        F.p95_bucket([v.p95_util for v in hist.vms]),
                        n_trees=sz.n_trees, seed=seed)
    return svc, hist, labels, arrivals


def planes(sz: Sizes, cluster_budget=None) -> PlaneBundle:
    return PlaneBundle(
        chassis_budget=ResourceVector(watts=CHASSIS_BUDGET_W),
        cluster_budget=cluster_budget,
        emergency=EmergencyConfig.from_model(CHASSIS_BUDGET_W),
        ballooning=BallooningConfig(),
        adaptive=AdaptiveConfig(blades_per_chassis=sz.blades),
        obs=Observability.full())


def drive(pipe, arrivals, sz: Sizes, seed: int):
    """Stream every arrival through `submit_to` over two ingest hosts,
    interleaved with departures of the oldest live admitted VMs and
    full-fleet power sweeps, then flush. Returns the (n,) decisions in
    arrival order (unit-clock stamps make merged order arrival order)
    and the number of departed VMs. The events depend only on the seed
    and on earlier decisions, so two pipelines that decide alike see
    the same stream."""
    rng = np.random.default_rng(seed + 1)
    streams = split_streams(arrivals, N_HOSTS, sz.batch // N_HOSTS)
    cores = np.asarray([v.cores for v in arrivals.vms], np.float32)
    mem = np.asarray([v.memory_gb for v in arrivals.vms], np.float32)
    parts, live = [], []
    departed = n_decided = 0
    cap_idx = np.arange(sz.n_chassis)

    def take(results):
        nonlocal n_decided
        for r in results:
            parts.append(r)
            live.extend(n_decided + np.nonzero(r.admitted)[0])
            n_decided += len(r.server)

    n_chunks = max(len(s) for s in streams)
    push = 0
    for j in range(n_chunks):
        for h in range(N_HOSTS):
            if j >= len(streams[h]):
                continue
            stamps, batch = streams[h][j]
            take(pipe.submit_to(h, batch, t=stamps))
            t_last = float(stamps[-1])
            push += 1
            if push % DEPART_EVERY == 0 and live:
                out = np.concatenate([p.server for p in parts])
                wt = np.concatenate([p.workload_type for p in parts])
                p95 = np.concatenate([p.p95_eff for p in parts])
                go = np.asarray(live[:DEPART_N])
                del live[:DEPART_N]
                departed += len(go)
                take(pipe.depart_to(
                    h, out[go], cores[go], p95[go], wt[go] == UF,
                    t=t_last + 0.25 + 1e-3 * np.arange(len(go)),
                    mem_gb=mem[go]))
            if push % CAP_EVERY == 0:
                power = CHASSIS_BUDGET_W * rng.uniform(0.85, 1.15,
                                                       sz.n_chassis)
                take(pipe.cap_to(
                    h, cap_idx, power,
                    t=t_last + 0.5 + 1e-3 * np.arange(sz.n_chassis)))
    tail = pipe.flush()
    if tail is not None:
        take([tail])
    servers = np.concatenate([p.server for p in parts])
    n = len(arrivals.vms)
    assert len(servers) == n, f"{len(servers)} decisions for {n} arrivals"
    rejected = sum(r.n_capacity_rejected + r.n_power_rejected
                   + r.n_token_rejected for r in parts)
    admitted = sum(r.n_admitted for r in parts)
    assert admitted + rejected == n, (admitted, rejected, n)
    return servers, departed


def check_cores(servers, departed, arrivals, sz: Sizes, state) -> None:
    """No server over its cores: the device's free cores equal the
    host replay of admitted minus departed cores, within [0, cores]."""
    free = np.asarray(state.free_cores, np.float64)
    assert (free >= -1e-3).all() and (free <= sz.cores + 1e-3).all(), \
        f"free cores outside [0, {sz.cores}]: {free.min()}..{free.max()}"
    used = np.zeros(len(free))
    cores = np.asarray([v.cores for v in arrivals.vms], np.float64)
    ok = servers >= 0
    np.add.at(used, servers[ok], cores[ok])
    # departures are the oldest admitted VMs, in admission order
    gone = np.nonzero(ok)[0][:departed]
    np.subtract.at(used, servers[gone], cores[gone])
    np.testing.assert_allclose(sz.cores - used, free, atol=1e-3)


def serve_pipe(svc, hist, labels, sz: Sizes, kernel: str):
    return ServePipeline.from_history(
        svc, hist, labels, n_servers=sz.n_chassis * sz.blades,
        cores_per_server=sz.cores, blades_per_chassis=sz.blades,
        config=ServeConfig(batch_size=sz.batch, kernel=kernel,
                           n_ingest_hosts=N_HOSTS, planes=planes(sz)))


def serve_phase(sz: Sizes, seed: int, clock: CompileClock,
                kernel: str) -> None:
    t0 = time.perf_counter()
    svc, hist, labels, arrivals = make_world(sz, seed)
    log("serve", setup_s=round(time.perf_counter() - t0, 3),
        history=sz.n_history, arrivals=sz.n_arrivals,
        servers=sz.n_chassis * sz.blades, trees=sz.n_trees)

    walls = []
    for _ in range(2):                      # cold (compiles), then warm
        c0, t0 = clock.seconds, time.perf_counter()
        pipe = serve_pipe(svc, hist, labels, sz, kernel)
        servers, departed = drive(pipe, arrivals, sz, seed)
        jax.block_until_ready(pipe.state)
        walls.append((time.perf_counter() - t0, clock.seconds - c0))
    reg = pipe.obs.registry
    alarms = pipe.alarms
    cut_w = reg.value("emergency_cut_watts_total")
    admitted = int((servers >= 0).sum())
    log("serve", kernel=kernel, compile_s=round(walls[0][1], 3),
        cold_wall_s=round(walls[0][0], 3),
        warm_wall_s=round(walls[1][0], 3),
        warm_compile_s=round(walls[1][1], 3), admitted=admitted,
        rejected=len(servers) - admitted, departed=departed,
        alarms=alarms, cut_w=round(float(cut_w), 1),
        ballooned_gb=round(pipe.ballooned_gb(), 1),
        adaptive_ratio=round(float(pipe.adaptive_ratio), 3))
    assert alarms > 0, "no power sweep raised an alarm"
    assert cut_w > 0, "no alarm applied a cap"
    check_cores(servers, departed, arrivals, sz, pipe.state)

    # each layer against the host: features and predictions on the
    # CPU, gated heads against the numpy oracle, then the whole stream
    # served again on the CPU
    cpu = jax.devices("cpu")[0]
    rows = [np.arange(lo, min(lo + sz.batch, sz.n_arrivals))
            for lo in range(0, sz.n_arrivals, sz.batch)]
    table_cpu = jax.device_put(pipe.table, cpu)
    x, x_cpu = (np.concatenate([
        np.asarray(featurize_batch(table, arrival_batch(arrivals, r),
                                   pad_to=sz.batch))[:len(r)]
        for r in rows]) for table in (pipe.table, table_cpu))
    np.testing.assert_allclose(x, x_cpu, rtol=1e-6)
    log("serve", features_bit_equal=int((x == x_cpu).all(-1).sum()),
        of=len(x))
    want = svc.query(x)
    packed, meta = pack_service(svc)
    got = jax.device_get(served_query(packed, meta, x, kernel=kernel))
    got_cpu = jax.device_get(served_query(
        jax.device_put(packed, cpu), meta, jax.device_put(x, cpu),
        kernel="pallas_interpret"))
    gate = svc.confidence_gate
    for head, conf in (("workload_type_used", "workload_conf"),
                       ("p95_bucket_used", "p95_conf")):
        np.testing.assert_allclose(got[conf], want[conf], atol=CONF_ATOL)
        tie = np.abs(want[conf] - gate) <= CONF_ATOL
        diff = got[head] != want[head]
        assert not (diff & ~tie).any(), \
            f"{head}: {int((diff & ~tie).sum())} arrivals disagree"
        log("serve", head=head, agree=int((~diff).sum()),
            at_gate=int(tie.sum()), cpu_kernel_agree=int(
                (got[head] == got_cpu[head]).sum()), of=len(diff))

    t0 = time.perf_counter()
    with jax.default_device(cpu):
        twin = serve_pipe(svc, hist, labels, sz, "pallas_interpret")
        servers_cpu, _ = drive(twin, arrivals, sz, seed)
    same = servers == servers_cpu
    log("serve", cpu_twin_agree=int(same.sum()), of=len(same),
        first_diff=int(np.argmin(same)) if not same.all() else -1,
        cpu_twin_s=round(time.perf_counter() - t0, 3))
    assert same.all(), "chip and CPU placements differ"


def fleet_phase(sz: Sizes, seed: int, clock: CompileClock) -> None:
    rng = np.random.default_rng(seed + 2)
    specs = paper_chassis_specs(balanced=True)
    layout = build_layout(specs)
    budgets = rng.uniform(*FLEET_BUDGET_W, sz.fleet_chassis)
    seeds = seed + np.arange(sz.fleet_chassis)
    duration = sz.fleet_steps * 0.2
    walls = []
    for _ in range(2):
        c0, t0 = clock.seconds, time.perf_counter()
        res = run_fleet(specs, budgets, "per_vm", duration, seeds,
                        backend="jax", layout=layout)
        walls.append((time.perf_counter() - t0, clock.seconds - c0))
    assert res.power_w.shape == (sz.fleet_chassis, sz.fleet_steps)
    assert np.isfinite(res.power_w).all()
    pick = np.sort(rng.choice(sz.fleet_chassis, sz.fleet_sampled,
                              replace=False))
    t0 = time.perf_counter()
    ref = run_fleet(specs, budgets[pick], "per_vm", duration, seeds[pick],
                    backend="numpy", layout=layout)
    oracle_s = time.perf_counter() - t0
    err = np.abs(ref.power_w - res.power_w[pick])
    log("fleet", chassis=sz.fleet_chassis, steps=sz.fleet_steps,
        compile_s=round(walls[0][1], 3), cold_wall_s=round(walls[0][0], 3),
        warm_wall_s=round(walls[1][0], 3), oracle_s=round(oracle_s, 3),
        sampled=len(pick), max_power_err_w=float(err.max()),
        max_nuf_freq_err=float(np.abs(
            ref.min_nuf_freq - res.min_nuf_freq[pick]).max()),
        rapl_frac=float(res.rapl_engaged_frac.mean()))
    assert err.max() <= POWER_TOL_W, \
        f"fleet power off the numpy oracle by {err.max()} W"


def sharded_phase(sz: Sizes, seed: int, clock: CompileClock,
                  kernel: str, n_shards: int) -> None:
    svc, hist, labels, arrivals = make_world(sz, seed)
    n_servers = sz.n_chassis * sz.blades
    idle = float(idle_power(F_MAX))
    allowance = sz.n_chassis * (CHASSIS_BUDGET_W - sz.blades * idle)
    budget = ResourceVector(
        watts=n_servers * idle + CLUSTER_POOL_FRAC * allowance)
    runs = {}
    for use_mesh in (True, False):
        c0, t0 = clock.seconds, time.perf_counter()
        pipe = ShardedServePipeline.from_history(
            svc, hist, labels, n_servers=n_servers,
            cores_per_server=sz.cores, blades_per_chassis=sz.blades,
            config=ShardedServeConfig(
                batch_size=sz.batch, kernel=kernel, n_shards=n_shards,
                use_shard_map=use_mesh, n_ingest_hosts=N_HOSTS,
                planes=planes(sz, cluster_budget=budget)))
        servers, departed = drive(pipe, arrivals, sz, seed)
        jax.block_until_ready(pipe.sharded)
        wall = time.perf_counter() - t0
        runs[use_mesh] = (pipe, servers)
        devices = {d for leaf in jax.tree.leaves(pipe.sharded.shards)
                   for d in leaf.devices()}
        tokens = int((servers == -3).sum())
        log("sharded", shard_map=use_mesh, shards=n_shards,
            compile_s=round(clock.seconds - c0, 3), wall_s=round(wall, 3),
            devices=len(devices), admitted=int((servers >= 0).sum()),
            token_rejected=tokens, alarms=pipe.alarms,
            spilled=pipe.spill_info["spilled"])
        if use_mesh:
            assert len(devices) == n_shards and all(
                d.platform == jax.devices()[0].platform
                for d in devices), f"state on {devices}"
            assert tokens > 0, "the token pools never bound"
        check_cores(servers, departed, arrivals, sz, pipe.global_state())
    (mesh_pipe, mesh_srv), (vmap_pipe, vmap_srv) = runs[True], runs[False]
    assert (mesh_srv == vmap_srv).all(), \
        f"{int((mesh_srv != vmap_srv).sum())} decisions differ"
    np.testing.assert_allclose(mesh_pipe.pool_left_vec(),
                               vmap_pipe.pool_left_vec(), rtol=1e-6)
    # the committed watt ledger never exceeds the budget's token pool
    gross = (budget.watts - n_servers * idle) \
        / ServerPowerModel().p_dyn_per_core
    committed = float(np.asarray(
        mesh_pipe.global_state().res_peak)[:, 0].sum())
    ratio = float(np.max(mesh_pipe.adaptive_ratio))
    assert committed <= gross * ratio + 1e-3, (committed, gross, ratio)
    log("sharded", decisions_equal=True, pools_equal=True,
        committed_rho=round(committed, 2), pool_rho=round(gross, 2),
        adaptive_ratio=round(ratio, 3))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes; accept the CPU")
    args = ap.parse_args(argv)

    devices = jax.devices()
    print(f"devices: {devices}", flush=True)
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        raise SystemExit(f"no TPU: JAX found {platform} devices")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} "
                         f"devices, found {len(devices)}")
    enable_compile_cache()
    clock = CompileClock()
    sz = REHEARSAL if args.rehearse else Sizes()
    kernel = "pallas" if platform == "tpu" else "pallas_interpret"
    if args.chips == 4:
        sharded_phase(sz, args.seed, clock, kernel, n_shards=4)
    else:
        serve_phase(sz, args.seed, clock, kernel)
        fleet_phase(sz, args.seed, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
