"""Online power-emergency plane (`repro.serve.emergency` /
`repro.serve.mitigation`) — oracle parity and invariants.

The contract under test (docs/emergency.md, DESIGN.md §12):

  * the batched apportionment equals an independent greedy numpy
    oracle built from `ChassisManager` / `PerVMController`, and the
    vmap and shard_map executions of the sharded emergency scan agree
    with the numpy kernel chassis-for-chassis;
  * `simulate(backend='serve-sharded')` with emergencies enabled stays
    decision-identical to the event-driven oracle at 1 shard and
    host-count-invariant at any shard count;
  * migration plans are deterministic and invariant to how their
    paired depart/arrive events are dealt across ingest hosts, and a
    full cap -> migrate -> uncap cycle conserves the power-token
    pools;
  * criticality-aware apportionment strictly beats the
    criticality-blind baseline on critical throttled-seconds over the
    same trace.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.capping import ChassisManager, PerVMController
from repro.core.fleet_dynamics import FREQ_TABLE
from repro.core.placement import ClusterState, SchedulerPolicy
from repro.core.power_model import N_PSTATES, ServerPowerModel, dyn_scale
from repro.serve import (CRIT_UF, EmergencyConfig, PlaneBundle,
                         ResourceVector, apply_caps_sharded,
                         chassis_rho_levels, device_state,
                         emergency_step, init_emergency,
                         init_emergency_sharded, masked_step,
                         mitigation_due, plan_migrations,
                         rho_pool_from_budget, sampled_power,
                         scatter_samples, shard_mesh, shard_state,
                         throttled_by_level)
from repro.serve.mitigation import LiveVMs
from repro.sim.scheduler_sim import (PredictionChannel, ServeBackendSpec,
                                     SimSpec, simulate)

#: The paper's 2x-oversubscription operating point: a 12-blade chassis
#: provisioned at 12 x 310 W peak, budgeted at half.
BUDGET_2X = 12 * 310.0 / 2.0

#: Stress budget for short tier-1 runs: barely above the static floor,
#: so alarms trip at any occupancy without simulating to midday.
BUDGET_TIGHT = 1480.0


def _cfg(budget=BUDGET_TIGHT, **kw) -> EmergencyConfig:
    return EmergencyConfig.from_model(budget, **kw)


def _loaded_state(seed, n_servers=48, per_chassis=12, cores=40, n=260):
    rng = np.random.default_rng(seed)
    st = ClusterState(n_servers=n_servers, cores_per_server=cores,
                      chassis_of_server=np.arange(n_servers) // per_chassis,
                      n_chassis=n_servers // per_chassis)
    for _ in range(n):
        srv = int(rng.integers(0, n_servers))
        c = int(rng.integers(1, 8))
        if st.free_cores[srv] >= c:
            st.place(srv, c, float(rng.uniform(0.2, 1)),
                     bool(rng.random() < 0.5))
    return st


# --- apportionment vs the greedy capping oracle ---------------------------

def _greedy_oracle(cut_w, dyn_w, floors, blind=False):
    """Independent per-chassis apportionment: explicit greedy loop over
    levels with a linear p-state search — deliberately NOT the
    branchless formulation under test."""
    fracs = 1.0 - dyn_scale(FREQ_TABLE)
    L = len(dyn_w)
    rem = max(float(cut_w), 0.0)
    total = sum(dyn_w)
    pstates, takes = [], []
    for lv in range(L):
        red_max = dyn_w[lv] * fracs[floors[lv]]
        if blind:
            want = min(rem if total <= 0 else
                       max(cut_w, 0.0) * dyn_w[lv] / total, red_max)
        else:
            want = min(rem, red_max)
        p = 0
        if dyn_w[lv] > 0 and want > 0:
            ratio = want / dyn_w[lv]
            while p < N_PSTATES and fracs[p] < ratio:
                p += 1
        takes.append(want)
        pstates.append(min(p, floors[lv]))
        if not blind:
            rem -= want
    leftover = max(max(float(cut_w), 0.0) - sum(takes), 0.0)
    return pstates, takes, leftover


@pytest.mark.parametrize("blind", [False, True])
def test_apportion_matches_greedy_oracle(blind):
    rng = np.random.default_rng(0)
    ctrl = PerVMController(ServerPowerModel(), 230.0)
    floors = (N_PSTATES - 1, 5)
    for _ in range(200):
        dyn = rng.uniform(0, 400, 2)
        if rng.random() < 0.3:
            dyn[rng.integers(0, 2)] = 0.0       # zero-util level
        cut = float(rng.uniform(-20, 500))
        ps, take, left = ctrl.apportion(cut, dyn, np.asarray(floors),
                                        blind=blind)
        ops, otake, oleft = _greedy_oracle(cut, dyn, floors, blind)
        np.testing.assert_array_equal(ps, ops)
        np.testing.assert_allclose(take, otake, atol=1e-9)
        assert left == pytest.approx(oleft, abs=1e-9)


def test_emergency_alarm_matches_chassis_manager():
    cfg = _cfg(BUDGET_2X)
    mgr = cfg.manager()
    assert isinstance(mgr, ChassisManager)
    rho = np.array([[10.0, 10.0], [150.0, 150.0], [40.0, 260.0]])
    st = init_emergency(3, xp=np, dtype=np.float64)
    st, out = emergency_step(cfg, st, rho, 0.9, 1.0, np)
    np.testing.assert_array_equal(out.alarm,
                                  mgr.poll(np.asarray(out.power_w)))
    # alarmed chassis with an achievable cut land at/below the budget
    ok = out.alarm & (out.leftover_w <= 1e-6)
    assert (out.power_after_w[ok] <= cfg.chassis_budget_w + 1e-6).all()


def test_emergency_hysteresis_lift_after_clear():
    """A cleared alarm holds the cap for `lift_after_s`, then restores
    nominal frequency (the paper's 30 s lift delay)."""
    cfg = _cfg(BUDGET_2X, lift_after_s=30.0)
    rho = np.array([[200.0, 200.0]])
    st = init_emergency(1, xp=np, dtype=np.float64)
    st, out = emergency_step(cfg, st, rho, 0.95, 0.0, np)   # alarm
    assert out.alarm[0] and (st.pstate > 0).any()
    st, out = emergency_step(cfg, st, rho, 0.10, 10.0, np)  # clear, hold
    assert not out.alarm[0] and (st.pstate > 0).any()
    assert st.clear_s[0] == pytest.approx(10.0)
    st, out = emergency_step(cfg, st, rho, 0.10, 45.0, np)  # lift
    assert not (st.pstate > 0).any() and not st.rapl[0]
    assert np.isinf(st.clear_s[0])


def test_throttled_seconds_accrue_per_level():
    cfg = _cfg(BUDGET_2X)
    rho = np.array([[300.0, 60.0]])       # NUF floor absorbs the cut
    st = init_emergency(1, xp=np, dtype=np.float64)
    st, _ = emergency_step(cfg, st, rho, 0.60, 0.0, np)
    assert st.pstate[0, 0] > 0 and st.pstate[0, 1] == 0
    st, _ = emergency_step(cfg, st, rho, 0.60, 7.0, np)
    assert throttled_by_level(st)[0] == pytest.approx(7.0)
    assert throttled_by_level(st)[CRIT_UF] == 0.0


# --- vmap == shard_map == numpy oracle ------------------------------------

def _dense_samples(cfg, n_chassis, rho_lv, util, t0):
    idx = np.arange(n_chassis)
    stamps = t0 + (idx + 1) * 1e-4
    power = np.asarray(sampled_power(
        cfg, rho_lv, util, np.zeros((n_chassis, 2), np.int32),
        np.zeros(n_chassis, bool), np))
    return idx, power, stamps


@pytest.mark.parametrize("use_mesh", [False, True])
def test_sharded_scan_matches_numpy_oracle(use_mesh):
    """apply_caps_sharded (vmap, and shard_map on a 4-device runtime)
    must reproduce the numpy kernel chassis-for-chassis, in x64
    bit-exactly."""
    mesh = shard_mesh(4) if use_mesh else None
    if use_mesh and mesh is None:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_"
                    "device_count=4")
    cfg = _cfg()
    st = _loaded_state(3)
    with jax.enable_x64(True):
        dst = device_state(st, jnp.float64)
        sharded = shard_state(dst, 4)
        emer = init_emergency_sharded(4, 4, dtype=jnp.float64)
        rho_lv = np.asarray(chassis_rho_levels(
            np.asarray(dst.gamma_nuf), np.asarray(dst.gamma_uf),
            np.asarray(dst.chassis_servers), np))
        ref = init_emergency(4, xp=np, dtype=np.float64)
        for t0, u in ((0.0, 0.9), (20.0, 0.4), (60.0, 0.95)):
            idx, power, stamps = _dense_samples(cfg, 4, rho_lv, u, t0)
            emer, out = apply_caps_sharded(cfg, sharded, emer, idx,
                                           power, stamps, mesh=mesh)
            pw, mask, ts = scatter_samples(4, idx, power, stamps, np,
                                           np.float64)
            ref, rout = masked_step(cfg, ref, rho_lv, pw, mask, ts, np)
            for a, b in zip(ref, emer):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b).reshape(a.shape))
            np.testing.assert_array_equal(
                np.asarray(rout.alarm),
                np.asarray(out.alarm).reshape(-1))


def test_sharded_rho_levels_match_global():
    st = _loaded_state(5)
    dst = device_state(st)
    sharded = shard_state(dst, 4)
    want = np.asarray(chassis_rho_levels(
        np.asarray(dst.gamma_nuf), np.asarray(dst.gamma_uf),
        np.asarray(dst.chassis_servers), np))
    got = np.stack([
        np.asarray(chassis_rho_levels(
            np.asarray(sharded.shards.gamma_nuf)[s],
            np.asarray(sharded.shards.gamma_uf)[s],
            np.asarray(sharded.shards.chassis_servers)[s], np))
        for s in range(4)]).reshape(4, 2)
    np.testing.assert_allclose(got, want, rtol=1e-6)


# --- capping edge cases (surfaced by the batched oracle) ------------------

def test_zero_util_level_takes_no_cut():
    """A level with zero dynamic draw must neither NaN nor be assigned
    a share (the zero-util division guard)."""
    ctrl = PerVMController(ServerPowerModel(), 230.0)
    ps, take, left = ctrl.apportion(50.0, np.array([0.0, 100.0]))
    assert np.isfinite(take).all() and take[0] == 0.0 and ps[0] == 0
    assert take[1] == pytest.approx(50.0) and left == 0.0


def test_all_critical_chassis_caps_before_rapl():
    """An all-critical chassis must cap its critical VMs down to their
    own floor before the leftover falls through to the RAPL backstop
    — not skip straight to the all-core throttle."""
    cfg = _cfg(BUDGET_2X)
    fracs = 1.0 - dyn_scale(FREQ_TABLE)
    dyn_crit = 300.0
    max_crit_cut = dyn_crit * fracs[cfg.floors[CRIT_UF]]
    # absorbable within the critical floor: capped, no RAPL
    ps, take, left = PerVMController(ServerPowerModel(), 230.0) \
        .apportion(0.8 * max_crit_cut, np.array([0.0, dyn_crit]),
                   np.asarray(cfg.floors))
    assert 0 < ps[1] <= cfg.floors[CRIT_UF] and left == 0.0
    # beyond the floor: critical pinned AT its floor, leftover > 0
    ps, take, left = PerVMController(ServerPowerModel(), 230.0) \
        .apportion(2.0 * max_crit_cut, np.array([0.0, dyn_crit]),
                   np.asarray(cfg.floors))
    assert ps[1] == cfg.floors[CRIT_UF] and left > 0
    # and the emergency step turns that leftover into the RAPL backstop
    st = init_emergency(1, xp=np, dtype=np.float64)
    rho = np.array([[0.0, 2.0 * max_crit_cut
                     / (cfg.p_dyn_per_core * 0.9)]])
    st, out = emergency_step(
        _cfg(BUDGET_2X, alert_fraction=0.5,
             target_margin_w=BUDGET_2X - cfg.static_w - 1.0),
        st, rho, 0.9, 0.0, np)
    assert st.rapl[0] and out.leftover_w[0] > 0


# --- sim backend identities -----------------------------------------------

SIM_KW = dict(days=0.1, seed=0, deployments_per_hour=16.0,
              prefill_core_ratio=0.6)


def _spec(cfg, backend="event", shards=1, hosts=1, **kw):
    """SimSpec on the shared short-sim settings with the emergency
    plane attached."""
    return SimSpec(serve=ServeBackendSpec(backend=backend, shards=shards,
                                          ingest_hosts=hosts),
                   emergency=cfg, **{**SIM_KW, **kw})


def test_one_shard_sim_identity_with_emergencies():
    """backend='serve-sharded' at 1 shard == the event oracle,
    trace-for-trace and emergency-metric-for-metric, with the plane
    alarming and migrating (every serve scan additionally asserts the
    jnp kernel equal to the numpy oracle in-sim)."""
    cfg = _cfg(dwell_s=120.0)
    tr_e, tr_s = [], []
    me = simulate(SchedulerPolicy(use_power_rule=False),
                  PredictionChannel("ml"), _spec(cfg), trace=tr_e)
    ms = simulate(SchedulerPolicy(use_power_rule=False),
                  PredictionChannel("ml"),
                  _spec(cfg, backend="serve-sharded"), trace=tr_s)
    assert me.alarms > 0
    assert tr_e == tr_s
    assert me.alarms == ms.alarms
    assert me.migrations == ms.migrations
    assert me.uf_throttled_s == ms.uf_throttled_s
    assert me.nuf_throttled_s == ms.nuf_throttled_s
    assert me.failure_rate == ms.failure_rate


@pytest.mark.parametrize("n_hosts", [2, 4])
def test_host_count_invariance_with_emergencies(n_hosts):
    """The full plane — arrivals, departures, emergencies, migrations
    — is invariant to the ingest host count at a fixed shard count."""
    cfg = _cfg(dwell_s=120.0)
    traces = []
    metrics = []
    for hosts in (1, n_hosts):
        tr = []
        metrics.append(simulate(
            SchedulerPolicy(use_power_rule=False),
            PredictionChannel("ml"),
            _spec(cfg, backend="serve-sharded", shards=2, hosts=hosts),
            trace=tr))
        traces.append(tr)
    assert traces[0] == traces[1]
    assert metrics[0].alarms == metrics[1].alarms
    assert metrics[0].migrations == metrics[1].migrations
    assert metrics[0].uf_throttled_s == metrics[1].uf_throttled_s


@pytest.mark.slow
def test_aware_beats_blind_at_2x_oversubscription():
    """The acceptance axis: at 2x oversubscription over the same
    trace, criticality-aware apportionment reports strictly lower
    critical throttled-seconds than the criticality-blind baseline
    (and both runs assert the budget invariant in-sim)."""
    kw = dict(days=0.55, seed=0, deployments_per_hour=16.0,
              prefill_core_ratio=0.75)
    aware = simulate(SchedulerPolicy(alpha=0.8),
                     PredictionChannel("ml"),
                     SimSpec(emergency=_cfg(BUDGET_2X, dwell_s=3600.0),
                             **kw))
    blind = simulate(SchedulerPolicy(alpha=0.8),
                     PredictionChannel("ml"),
                     SimSpec(emergency=_cfg(BUDGET_2X, dwell_s=3600.0,
                                            criticality_blind=True),
                             **kw))
    assert aware.alarms > 0
    assert 0 <= aware.uf_throttled_s < blind.uf_throttled_s


def test_aware_beats_blind_tight_budget():
    """Fast tier-1 twin of the 2x acceptance check on the stress
    budget: same trace, strictly lower critical throttled-seconds."""
    cfg_kw = dict(dwell_s=3600.0)
    aware = simulate(SchedulerPolicy(alpha=0.8), PredictionChannel("ml"),
                     _spec(_cfg(**cfg_kw)))
    blind = simulate(SchedulerPolicy(alpha=0.8), PredictionChannel("ml"),
                     _spec(_cfg(criticality_blind=True, **cfg_kw)))
    assert aware.alarms > 0
    assert aware.uf_throttled_s < blind.uf_throttled_s
    assert aware.nuf_throttled_s > 0


# --- migration planning ---------------------------------------------------

def _mig_world():
    """A cluster with one overloaded chassis full of critical VMs and
    plenty of headroom elsewhere."""
    st = _loaded_state(0, n_servers=48, per_chassis=12, n=0)
    rng = np.random.default_rng(7)
    rows = []
    for v in range(24):
        srv = int(rng.integers(0, 12))          # chassis 0
        if st.free_cores[srv] < 8:
            continue
        p95 = float(rng.uniform(0.6, 0.95))
        st.place(srv, 8, p95, True)
        rows.append((srv, 8.0, p95, True))
    for v in range(10):                          # background elsewhere
        srv = int(rng.integers(12, 48))
        p95 = float(rng.uniform(0.2, 0.5))
        st.place(srv, 4, p95, False)
        rows.append((srv, 4.0, p95, False))
    live = LiveVMs(np.array([r[0] for r in rows], np.int32),
                   np.array([r[1] for r in rows]),
                   np.array([r[2] for r in rows]),
                   np.array([r[3] for r in rows], bool))
    return st, live


def test_plan_migrations_moves_cheapest_critical_to_headroom():
    cfg = _cfg()
    st, live = _mig_world()
    rho_lv = np.zeros((4, 2))
    np.add.at(rho_lv, (np.asarray(st.chassis_of_server)[live.server],
                       live.is_uf.astype(int)),
              live.p95_eff * live.cores)
    due = np.array([True, False, False, False])
    plan = plan_migrations(cfg, live, st.chassis_of_server,
                           st.free_cores, rho_lv, 0.9, due,
                           max_moves_per_chassis=4)
    assert len(plan) > 0
    assert (np.asarray(st.chassis_of_server)[plan.src_server] == 0).all()
    assert (np.asarray(st.chassis_of_server)[plan.dst_server] != 0).all()
    assert plan.is_uf.all()
    # cheapest-first: the planned rho sequence is non-decreasing
    w = plan.p95_eff * plan.cores
    assert (np.diff(w) >= -1e-12).all()
    # determinism
    plan2 = plan_migrations(cfg, live, st.chassis_of_server,
                            st.free_cores, rho_lv, 0.9, due,
                            max_moves_per_chassis=4)
    np.testing.assert_array_equal(plan.dst_server, plan2.dst_server)


def test_migration_events_invariant_to_host_dealing(serve_world):
    """Pushing the plan's paired depart/arrive events through the
    ingest mux must yield the same final sharded state for any host
    dealing (PR 4's invariance carrying over to kind 3's siblings)."""
    from repro.serve import ShardedServeConfig, ShardedServePipeline
    svc, hist, labels, _ = serve_world
    st, live = _mig_world()
    cfg = _cfg()
    rho_lv = np.zeros((4, 2))
    np.add.at(rho_lv, (np.asarray(st.chassis_of_server)[live.server],
                       live.is_uf.astype(int)),
              live.p95_eff * live.cores)
    plan = plan_migrations(cfg, live, st.chassis_of_server,
                           st.free_cores, rho_lv, 0.9,
                           np.array([True, False, False, False]),
                           max_moves_per_chassis=4)
    assert len(plan) >= 2
    dep, arr = plan.as_events()
    dep_t, arr_t = plan.paired_stamps(100.0)
    finals = []
    for n_hosts, deal in ((1, None), (3, "round-robin")):
        from repro.serve.featurizer import table_from_history
        cap = max(v.subscription for v in hist.vms) + 8
        pipe = ShardedServePipeline(
            svc, table_from_history(hist, labels, cap),
            device_state(st), cores_per_server=40,
            blades_per_chassis=12,
            config=ShardedServeConfig(batch_size=32, n_shards=4,
                                      n_ingest_hosts=n_hosts,
                                      planes=PlaneBundle(emergency=cfg)))
        # interleave all 2M rows in stamp order, dealt across hosts
        rows = sorted(
            [(dep_t[i], i, dep) for i in range(len(plan))]
            + [(arr_t[i], i, arr) for i in range(len(plan))])
        for k, (t, i, b) in enumerate(rows):
            pipe.depart_to(k % n_hosts, b.server[i:i + 1],
                           b.cores[i:i + 1], b.p95_eff[i:i + 1],
                           b.is_uf[i:i + 1], t=np.array([t]))
        pipe.flush()
        finals.append(pipe.global_state())
    for a, b in zip(finals[0], finals[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)


def test_token_pool_conserved_through_cap_migrate_uncap(serve_world):
    """A full emergency lifecycle on the sharded pipeline: cap events
    raise the alarm, the migration pair moves a critical VM across
    shards (credit + debit), the uncap sample lifts the cap — and the
    token pools balance to the admitted rho throughout."""
    from repro.serve import ShardedServeConfig, ShardedServePipeline
    from repro.serve.featurizer import table_from_history
    svc, hist, labels, _ = serve_world
    st, live = _mig_world()
    cfg = _cfg(lift_after_s=5.0)
    budget_w = 48 * 112.0 + 2000.0
    cap = max(v.subscription for v in hist.vms) + 8
    pipe = ShardedServePipeline(
        svc, table_from_history(hist, labels, cap), device_state(st),
        cores_per_server=40, blades_per_chassis=12,
        config=ShardedServeConfig(
            batch_size=32, n_shards=4,
            planes=PlaneBundle(
                cluster_budget=ResourceVector(watts=budget_w),
                emergency=cfg)))
    pool0 = rho_pool_from_budget(budget_w, 48, pipe.power_model)
    rho0 = float(np.asarray(pipe.global_state().rho_peak).sum())
    np.testing.assert_allclose(pipe.pool_left().sum(), pool0 - rho0,
                               rtol=1e-5)
    # cap: chassis 0 samples hot
    pipe.cap_to(0, [0], [2200.0], t=np.array([1.0]))
    assert pipe.alarms == 1
    assert (np.asarray(pipe.emergency.pstate) > 0).any()
    # migrate: paired events through the single queue
    rho_lv = np.zeros((4, 2))
    np.add.at(rho_lv, (np.asarray(st.chassis_of_server)[live.server],
                       live.is_uf.astype(int)),
              live.p95_eff * live.cores)
    plan = plan_migrations(cfg, live, st.chassis_of_server,
                           st.free_cores, rho_lv, 0.9,
                           np.array([True, False, False, False]))
    assert len(plan) > 0
    dep, arr = plan.as_events()
    dep_t, arr_t = plan.paired_stamps(2.0)
    for i in range(len(plan)):          # pairs in stamp order
        for b, ts in ((dep, dep_t), (arr, arr_t)):
            pipe.depart_to(0, b.server[i:i + 1], b.cores[i:i + 1],
                           b.p95_eff[i:i + 1], b.is_uf[i:i + 1],
                           t=ts[i:i + 1])
    pipe.flush()
    back = pipe.global_state()
    rho1 = float(np.asarray(back.rho_peak).sum())
    np.testing.assert_allclose(rho1, rho0, rtol=1e-5)     # moved, not lost
    np.testing.assert_allclose(pipe.pool_left().sum(), pool0 - rho1,
                               rtol=1e-4)
    # the moved rho actually changed chassis
    assert np.asarray(back.rho_peak)[0] < rho_lv.sum(-1)[0] - 1e-6
    # uncap: cool samples past the lift window restore nominal
    pipe.cap_to(0, [0], [1200.0], t=np.array([10.0]))
    pipe.cap_to(0, [0], [1200.0], t=np.array([20.0]))
    assert not (np.asarray(pipe.emergency.pstate) > 0).any()
    np.testing.assert_allclose(pipe.pool_left().sum(), pool0 - rho1,
                               rtol=1e-4)


# --- pipeline cap-event plumbing ------------------------------------------

@pytest.fixture(scope="module")
def serve_world():
    from repro.core import features as F
    from repro.core.predictor import train_service
    from repro.sim.telemetry import generate_population
    pop = generate_population(400, seed=0)
    hist, arrivals = F.split_history_arrivals(pop)
    labels = hist.labels.astype(np.float64)
    aggs = F.subscription_aggregates(hist, labels)
    svc = train_service(F.build_features(hist, aggs),
                       labels.astype(np.int64),
                       F.p95_bucket([v.p95_util for v in hist.vms]),
                       n_trees=12)
    return svc, hist, labels, arrivals


def test_cap_events_permutation_invariant_across_hosts(serve_world):
    """Dealing the same stamped power samples across different host
    counts must leave identical emergency state (kind-3 events obey
    the same merge contract as arrivals/departures)."""
    from repro.serve import ServeConfig, ServePipeline
    svc, hist, labels, _ = serve_world
    samples = [(float(t), c, p) for t, c, p in zip(
        np.arange(1.0, 13.0),
        [0, 1, 2, 3, 0, 1, 2, 3, 0, 1, 2, 3],
        [2200.0, 1300.0, 2400.0, 1350.0, 1250.0, 2300.0,
         1200.0, 2250.0, 2350.0, 1280.0, 1320.0, 1400.0])]
    states = []
    for n_hosts in (1, 3):
        pipe = ServePipeline.from_history(
            svc, hist, labels, n_servers=48, cores_per_server=40,
            blades_per_chassis=12,
            config=ServeConfig(batch_size=32, n_ingest_hosts=n_hosts,
                               planes=PlaneBundle(emergency=_cfg())))
        for k, (t, c, p) in enumerate(samples):
            pipe.cap_to(k % n_hosts, [c], [p], t=np.array([t]))
        pipe.flush()
        states.append(pipe.emergency)
    for a, b in zip(states[0], states[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cap_to_requires_emergency_cfg(serve_world):
    from repro.serve import ServePipeline
    svc, hist, labels, _ = serve_world
    pipe = ServePipeline.from_history(
        svc, hist, labels, n_servers=48, cores_per_server=40,
        blades_per_chassis=12)
    with pytest.raises(ValueError):
        pipe.cap_to(0, [0], [2000.0])


def test_mitigation_due_and_dwell_reset():
    cfg = _cfg(BUDGET_2X, dwell_s=20.0)
    rho = np.array([[40.0, 400.0]])       # critical-heavy: UF capped
    st = init_emergency(1, xp=np, dtype=np.float64)
    for t in (0.0, 10.0, 25.0):
        st, out = emergency_step(cfg, st, rho, 0.95, t, np)
        assert out.alarm[0]
    assert mitigation_due(cfg, st, np)[0]
    from repro.serve import reset_dwell
    st = reset_dwell(st, np.array([True]), np)
    assert not mitigation_due(cfg, st, np)[0]
