"""Online serving pipeline (`repro.serve`) — parity and behavior.

The serve subsystem is a device twin of existing host code, so almost
every test is an oracle comparison: jnp featurizer vs
`core/features.py`, batched inference vs `PredictionService.query`,
batched placement vs `SchedulerPolicy.choose` stepped one arrival at a
time, and the scheduler simulation's serve backend vs the event-driven
oracle."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import features as F
from repro.core.placement import (ClusterState, SchedulerPolicy,
                                  _score_chassis_scalar,
                                  _score_server_scalar)
from repro.core.predictor import train_service
from repro.serve import (FAIL_CAPACITY, FAIL_POWER, PlaneBundle,
                         ResourceVector, ServeConfig,
                         ServePipeline, device_state, featurize_batch,
                         headroom_w, pack_service, place_batch,
                         projected_chassis_power, remove_batch,
                         rho_cap_from_budget, score_chassis_batch,
                         score_server_batch, served_query,
                         table_from_history)
from repro.sim.telemetry import (arrival_batch, generate_population,
                                 stream_arrivals)


@pytest.fixture(scope="module")
def world():
    pop = generate_population(700, seed=0)
    hist, arrivals = F.split_history_arrivals(pop)
    labels = hist.labels.astype(np.float64)
    aggs = F.subscription_aggregates(hist, labels)
    svc = train_service(F.build_features(hist, aggs),
                        labels.astype(np.int64),
                        F.p95_bucket([v.p95_util for v in hist.vms]),
                        n_trees=12)
    cap = max(v.subscription for v in pop.vms) + 8
    table = table_from_history(hist, labels, cap)
    return dict(pop=pop, hist=hist, arrivals=arrivals, labels=labels,
                aggs=aggs, svc=svc, table=table)


# --- featurizer parity ----------------------------------------------------

def test_featurizer_matches_numpy_oracle(world):
    want = F.build_features(world["arrivals"], world["aggs"])
    got = np.asarray(featurize_batch(world["table"],
                                     arrival_batch(world["arrivals"])))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_featurizer_incremental_equals_bulk(world):
    hist, labels = world["hist"], world["labels"]
    n = len(hist.vms) // 2
    cap = world["table"].capacity
    t2 = table_from_history(F.Population(vms=hist.vms[:n]), labels[:n],
                            cap)
    pipe_like = table_from_history(F.Population(vms=hist.vms[n:]),
                                   labels[n:], cap)
    merged = type(t2)(*(a + b for a, b in zip(t2, pipe_like)))
    for a, b in zip(merged, world["table"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3)


def test_p95_bucket_boundaries_match_host_in_f32():
    """Exact bucket edges (integer-percent telemetry) must bucket like
    the f64 host despite f32 inputs — the host's 1e-9 epsilon
    underflows in f32, the ceil formulation does not."""
    from repro.serve.featurizer import p95_bucket_jnp
    vals = np.array([0.0, 1.0, 24.999, 25.0, 25.001, 50.0, 74.5, 75.0,
                     99.0, 100.0])
    want = F.p95_bucket(vals.astype(np.float64))
    got = np.asarray(p95_bucket_jnp(jnp.asarray(vals, jnp.float32)))
    np.testing.assert_array_equal(got, want)


def test_featurizer_default_row_for_unseen_subscription(world):
    b = arrival_batch(world["arrivals"], [0])
    b.subscription[:] = world["table"].capacity - 1    # never observed
    got = np.asarray(featurize_batch(world["table"], b))[0]
    assert got[0] == pytest.approx(F._DEFAULT_AGG["pct_uf"])
    assert got[2] == 0.0                               # sub_total_vms
    np.testing.assert_allclose(got[3:7], F._DEFAULT_AGG["bucket_mix"])


def test_featurizer_out_of_capacity_ids_fall_back_and_drop(world):
    from repro.serve import update_table
    table = world["table"]
    cap = table.capacity
    # featurize: an id past capacity must get the default row, not a
    # clamped gather of the last populated row
    b = arrival_batch(world["arrivals"], [0])
    b.subscription[:] = cap + 5
    got = np.asarray(featurize_batch(table, b))[0]
    assert got[0] == pytest.approx(F._DEFAULT_AGG["pct_uf"])
    assert got[2] == 0.0
    # update: an id past capacity is dropped, not wrapped/clamped
    t2 = update_table(table, jnp.asarray([cap + 5, -3], jnp.int32),
                      jnp.ones(2), jnp.ones(2) * 200.0,
                      jnp.ones(2) * 50.0, jnp.ones(2) * 30.0)
    for a, b_ in zip(t2, table):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_featurizer_padding_rows_dropped(world):
    b = arrival_batch(world["arrivals"], np.arange(5))
    unpadded = np.asarray(featurize_batch(world["table"], b))
    padded = np.asarray(featurize_batch(world["table"], b, pad_to=16))
    np.testing.assert_array_equal(padded[:5], unpadded)
    assert padded.shape[0] == 16


# --- batched inference ----------------------------------------------------

def test_served_query_matches_prediction_service(world):
    x = F.build_features(world["arrivals"], world["aggs"])
    want = world["svc"].query(x)
    packed, meta = pack_service(world["svc"])
    got = served_query(packed, meta, jnp.asarray(x), kernel="ref")
    np.testing.assert_allclose(np.asarray(got["workload_conf"]),
                               want["workload_conf"], atol=1e-5)
    np.testing.assert_allclose(np.asarray(got["p95_conf"]),
                               want["p95_conf"], atol=1e-5)
    for k in ("workload_type_used", "p95_bucket_used"):
        agree = (np.asarray(got[k]) == want[k]).mean()
        assert agree >= 0.995, f"{k} agreement {agree}"


def test_served_query_pallas_interpret_matches_ref(world):
    x = F.build_features(world["arrivals"], world["aggs"])[:8]
    packed, meta = pack_service(world["svc"])
    ref = served_query(packed, meta, jnp.asarray(x), kernel="ref")
    pal = served_query(packed, meta, jnp.asarray(x),
                       kernel="pallas_interpret")
    np.testing.assert_allclose(np.asarray(pal["workload_conf"]),
                               np.asarray(ref["workload_conf"]),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(pal["p95_bucket_used"]),
                                  np.asarray(ref["p95_bucket_used"]))


def test_served_query_conservative_fallback(world):
    x = F.build_features(world["arrivals"], world["aggs"])
    packed, meta = pack_service(world["svc"])
    got = served_query(packed, meta, jnp.asarray(x), kernel="ref")
    cons = np.asarray(got["conservative"])
    wt = np.asarray(got["workload_type_used"])
    pb = np.asarray(got["p95_bucket_used"])
    low_wt = np.asarray(got["workload_conf"]) < meta.confidence_gate
    low_pb = np.asarray(got["p95_conf"]) < meta.confidence_gate
    np.testing.assert_array_equal(cons, low_wt | low_pb)
    assert (wt[low_wt] == 1).all()          # UF fallback
    assert (pb[low_pb] == 3).all()          # bucket-4 fallback


# --- batched placement vs the scalar/sequential oracles -------------------

def _loaded_state(seed, n_servers=24, per_chassis=4, cores=40, n=60):
    rng = np.random.default_rng(seed)
    st = ClusterState(n_servers=n_servers, cores_per_server=cores,
                      chassis_of_server=np.arange(n_servers) // per_chassis,
                      n_chassis=n_servers // per_chassis)
    for _ in range(n):
        srv = int(rng.integers(0, n_servers))
        c = int(rng.integers(1, 8))
        if st.free_cores[srv] < c:
            continue
        st.place(srv, c, float(rng.uniform(0, 1)),
                 bool(rng.random() < 0.5))
    return st


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_score_batches_match_scalar_oracles(seed):
    st = _loaded_state(seed)
    dst = device_state(st)
    kappa = np.asarray(score_chassis_batch(dst))
    for c in range(st.n_chassis):
        assert kappa[c] == pytest.approx(_score_chassis_scalar(st, c),
                                         abs=1e-6)
    for uf in (True, False):
        eta = np.asarray(score_server_batch(dst, uf, 40))
        for s in range(st.n_servers):
            assert eta[s] == pytest.approx(
                _score_server_scalar(st, s, uf), abs=1e-6)
    # batched over arrival types: (B, S)
    eta2 = np.asarray(score_server_batch(dst, np.array([True, False]), 40))
    np.testing.assert_allclose(eta2[0],
                               np.asarray(score_server_batch(dst, True,
                                                             40)))


@pytest.mark.parametrize("policy", [
    SchedulerPolicy(alpha=0.8),
    SchedulerPolicy(alpha=0.0),
    SchedulerPolicy(alpha=0.8, packing_weight=0.0),   # Algorithm-1 mode
    SchedulerPolicy(power_weight=0.0),                # best-fit mode
    SchedulerPolicy(use_power_rule=False),
])
def test_place_batch_matches_sequential_choose_x64(policy):
    """The key equivalence: one x64 scan == `choose`+`place` stepped
    per arrival, on a randomized part-loaded cluster (both fast and
    subset-rank paths exercised via large/small arrivals)."""
    st = _loaded_state(3, n_servers=36, per_chassis=12, n=200)
    rng = np.random.default_rng(7)
    B = 48
    cores = rng.choice([1, 2, 4, 8, 16, 32], B).astype(np.float64)
    is_uf = rng.random(B) < 0.4
    p95 = rng.uniform(0.05, 1.0, B)
    st_np = copy.deepcopy(st)
    want = []
    for i in range(B):
        s = policy.choose(st_np, int(cores[i]), bool(is_uf[i]))
        want.append(-1 if s is None else s)
        if s is not None:
            st_np.place(s, int(cores[i]), float(p95[i]), bool(is_uf[i]))
    with jax.enable_x64(True):
        dst, srvs = place_batch(
            device_state(st, jnp.float64), cores, is_uf, p95,
            np.ones(B, bool), np.full(st.n_chassis, np.inf), policy,
            st.cores_per_server)
        got = [int(x) for x in np.asarray(srvs)]
    assert got == want
    np.testing.assert_allclose(np.asarray(dst.free_cores),
                               st_np.free_cores)
    np.testing.assert_allclose(np.asarray(dst.rho_peak), st_np.rho_peak)


def test_place_batch_f32_close_to_oracle():
    """The f32 serving path may flip rare near-tie ranks; the bound we
    document in DESIGN.md §9 is checked here."""
    st = _loaded_state(4, n_servers=36, per_chassis=12, n=200)
    rng = np.random.default_rng(8)
    B = 64
    cores = rng.choice([1, 2, 4, 8], B).astype(np.float32)
    is_uf = rng.random(B) < 0.4
    p95 = rng.uniform(0.05, 1.0, B).astype(np.float32)
    policy = SchedulerPolicy(alpha=0.8)
    st_np = copy.deepcopy(st)
    want = []
    for i in range(B):
        s = policy.choose(st_np, int(cores[i]), bool(is_uf[i]))
        want.append(-1 if s is None else s)
        if s is not None:
            st_np.place(s, int(cores[i]), float(p95[i]), bool(is_uf[i]))
    _, srvs = place_batch(device_state(st), cores, is_uf, p95,
                          np.ones(B, bool),
                          np.full(st.n_chassis, np.inf, np.float32),
                          policy, st.cores_per_server)
    agree = np.mean(np.asarray(srvs) == np.asarray(want))
    assert agree >= 0.9


def test_place_batch_padding_and_capacity_failure():
    st = ClusterState(n_servers=2, cores_per_server=4,
                      chassis_of_server=np.array([0, 1]), n_chassis=2)
    dst = device_state(st)
    cores = np.array([4, 4, 1, 7], np.float32)
    valid = np.array([True, True, True, False])
    dst, srvs = place_batch(dst, cores, np.ones(4, bool),
                            np.full(4, 0.5, np.float32), valid,
                            np.full(2, np.inf, np.float32),
                            SchedulerPolicy(), 4)
    srvs = np.asarray(srvs)
    assert set(srvs[:2]) == {0, 1}
    assert srvs[2] == FAIL_CAPACITY            # cluster is full
    assert np.asarray(dst.free_cores).sum() == 0


def test_admission_rejects_over_budget_and_leaves_state():
    st = ClusterState(n_servers=4, cores_per_server=40,
                      chassis_of_server=np.zeros(4, np.int64),
                      n_chassis=1)
    dst = device_state(st)
    # cap admits ~one 20-core @ p95=1.0 placement
    rho_cap = np.array([25.0], np.float32)
    cores = np.full(3, 20.0, np.float32)
    dst2, srvs = place_batch(dst, cores, np.ones(3, bool),
                             np.ones(3, np.float32), np.ones(3, bool),
                             rho_cap, SchedulerPolicy(), 40)
    srvs = np.asarray(srvs)
    assert (srvs >= 0).sum() == 1
    assert (srvs == FAIL_POWER).sum() == 2
    assert np.asarray(dst2.rho_peak)[0] == pytest.approx(20.0)
    # rejected placements must not have mutated free cores
    assert np.asarray(dst2.free_cores).sum() == pytest.approx(160 - 20)


def test_rho_cap_and_headroom_roundtrip():
    cap = rho_cap_from_budget(2450.0, 12, 3)
    assert cap.shape == (3,)
    st = ClusterState(n_servers=36, cores_per_server=40,
                      chassis_of_server=np.arange(36) // 12, n_chassis=3)
    st.place(0, 10, 0.8, True)
    dst = device_state(st)
    proj = projected_chassis_power(dst, 12)
    head = headroom_w(dst, 2450.0, 12)
    np.testing.assert_allclose(proj + head, 2450.0, rtol=1e-5)
    # the admission inequality and the watt headroom agree in sign
    assert (np.asarray(dst.rho_peak) <= cap).all() == (head >= 0).all()


def test_headroom_none_budget_is_infinite():
    st = ClusterState(n_servers=12, cores_per_server=40,
                      chassis_of_server=np.zeros(12, np.int64),
                      n_chassis=1)
    assert np.isinf(headroom_w(device_state(st), None, 12)).all()


def test_place_remove_roundtrip_bit_exact_x64():
    st = _loaded_state(6)
    cores = np.array([4.0, 8.0])
    uf = np.array([True, False])
    p95 = np.array([0.7318291, 0.2912347])
    with jax.enable_x64(True):
        dst0 = device_state(st, jnp.float64)
        dst, srvs = place_batch(dst0, cores, uf, p95, np.ones(2, bool),
                                np.full(st.n_chassis, np.inf),
                                SchedulerPolicy(), 40)
        dst = remove_batch(dst, srvs, cores, p95, uf)
        for a, b in zip(dst, dst0):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_remove_batch_roundtrip():
    st = _loaded_state(5)
    dst0 = device_state(st)
    cores = np.array([4, 2], np.float32)
    uf = np.array([True, False])
    p95 = np.array([0.7, 0.3], np.float32)
    dst, srvs = place_batch(dst0, cores, uf, p95, np.ones(2, bool),
                            np.full(st.n_chassis, np.inf, np.float32),
                            SchedulerPolicy(), 40)
    dst = remove_batch(dst, srvs, cores, p95, uf)
    for a, b in zip(dst, dst0):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)
    # negative server codes are ignored
    dst = remove_batch(dst, np.array([FAIL_CAPACITY]),
                       np.array([4.0], np.float32),
                       np.array([0.5], np.float32), np.array([True]))
    np.testing.assert_allclose(np.asarray(dst.free_cores),
                               np.asarray(dst0.free_cores), atol=1e-5)


# --- pipeline -------------------------------------------------------------

def test_pipeline_end_to_end_counts(world):
    pipe = ServePipeline.from_history(
        world["svc"], world["hist"], world["labels"], n_servers=36,
        cores_per_server=40, blades_per_chassis=12,
        config=ServeConfig(batch_size=32))
    results = []
    for _, b in stream_arrivals(world["arrivals"], 20):
        results += pipe.submit(b)
    tail = pipe.flush()
    if tail is not None:
        results.append(tail)
    total = sum(len(r.server) for r in results)
    assert total == len(world["arrivals"].vms)
    assert pipe.served == total
    admitted = sum(r.n_admitted for r in results)
    assert admitted > 0
    for r in results:
        ok = r.server >= 0
        assert (r.server[ok] < 36).all()
        assert r.n_admitted + r.n_capacity_rejected \
            + r.n_power_rejected == len(r.server)


def test_pipeline_hot_swap_drops_no_arrivals(world):
    pipe = ServePipeline.from_history(
        world["svc"], world["hist"], world["labels"], n_servers=36,
        cores_per_server=40, blades_per_chassis=12,
        config=ServeConfig(batch_size=16))
    first = pipe.submit(arrival_batch(world["arrivals"], np.arange(24)))
    svc2 = train_service(
        F.build_features(world["hist"], world["aggs"]),
        world["labels"].astype(np.int64),
        F.p95_bucket([v.p95_util for v in world["hist"].vms]),
        n_trees=12, seed=9)
    pipe.hot_swap(svc2)                  # 8 arrivals still queued
    rest = pipe.flush()
    served = sum(len(r.server) for r in first) + len(rest.server)
    assert served == 24
    assert pipe.swaps == 1
    # the standby model now serves
    out = pipe.serve(arrival_batch(world["arrivals"], np.arange(24, 40)))
    assert len(out.server) == 16


def test_pipeline_power_budget_rejects(world):
    tight = ServePipeline.from_history(
        world["svc"], world["hist"], world["labels"], n_servers=24,
        cores_per_server=40, blades_per_chassis=12,
        config=ServeConfig(
            batch_size=64,
            planes=PlaneBundle(chassis_budget=ResourceVector(
                watts=12 * 112.0 + 40.0))))  # ~no dynamic headroom
    res = tight.serve(arrival_batch(world["arrivals"], np.arange(64)))
    assert res.n_power_rejected > 0
    assert (tight.chassis_headroom_w(12 * 112.0 + 40.0) >= -1e-3).all()


def test_pipeline_observe_updates_aggregates(world):
    pipe = ServePipeline.from_history(
        world["svc"], world["hist"], world["labels"], n_servers=12,
        cores_per_server=40, blades_per_chassis=12)
    before = float(np.asarray(pipe.table.count).sum())
    pipe.observe(F.Population(vms=world["arrivals"].vms[:10]),
                 np.ones(10))
    after = float(np.asarray(pipe.table.count).sum())
    assert after == pytest.approx(before + 10)


# --- streaming arrivals ---------------------------------------------------

def test_stream_arrivals_covers_population(world):
    pop = world["arrivals"]
    seen = 0
    last_t = 0.0
    for t, b in stream_arrivals(pop, 33, arrival_rate_per_s=10.0):
        assert t > last_t
        last_t = t
        assert len(b) <= 33
        seen += len(b)
    assert seen == len(pop.vms)


# --- scheduler simulation backend ----------------------------------------

def test_scheduler_serve_backend_reproduces_event_oracle():
    """Acceptance: for the same arrival sequence and fixed predictions,
    backend='serve' reproduces the event-driven scheduler's placements
    decision-for-decision (x64 scan == f64 host rule)."""
    from repro.sim.scheduler_sim import (PredictionChannel,
                                         ServeBackendSpec, SimSpec,
                                         simulate)
    tr_e, tr_s = [], []
    e = simulate(SchedulerPolicy(alpha=0.8), PredictionChannel("ml"),
                 SimSpec(days=1.0, seed=0), trace=tr_e)
    s = simulate(SchedulerPolicy(alpha=0.8), PredictionChannel("ml"),
                 SimSpec(days=1.0, seed=0,
                         serve=ServeBackendSpec(backend="serve")),
                 trace=tr_s)
    assert tr_e == tr_s
    assert e.failure_rate == s.failure_rate
    assert e.chassis_score_std == s.chassis_score_std
    assert e.server_score_std == s.server_score_std
    assert e.empty_server_ratio == s.empty_server_ratio


def test_scheduler_serve_backend_admission_budget():
    from repro.sim.scheduler_sim import (PredictionChannel,
                                         ServeBackendSpec, SimSpec,
                                         simulate)
    free = simulate(SchedulerPolicy(alpha=0.8), PredictionChannel("ml"),
                    SimSpec(days=0.5, seed=0,
                            serve=ServeBackendSpec(backend="serve")))
    tight = simulate(
        SchedulerPolicy(alpha=0.8), PredictionChannel("ml"),
        SimSpec(days=0.5, seed=0, serve=ServeBackendSpec(
            backend="serve",
            admission_budget=ResourceVector(watts=12 * 112.0 + 60.0))))
    # ~60 W of dynamic headroom per chassis power-rejects a large
    # share of placements that an unbudgeted run admits freely
    assert free.failure_rate < 0.01
    assert tight.failure_rate > 0.2
