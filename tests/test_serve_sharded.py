"""Sharded serve placement (`repro.serve.sharding`) — equivalence and
invariants.

The contract under test (docs/sharding.md):

  * 1 shard is *decision-identical* to the unsharded serve path (and
    therefore, in x64, to the event-driven scheduler oracle);
  * N shards never exceed the global watt budget their token pools
    encode, whatever the spillover traffic does;
  * the whole protocol — routing, reserve, spillover commit — is a
    deterministic function of the batch under a fixed seed;
  * the vmap and shard_map executions of the per-shard scans agree
    (the shard_map leg needs >= 4 devices:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` — CI's
    sharded smoke job; it skips elsewhere).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import features as F
from repro.core.placement import ClusterState, SchedulerPolicy
from repro.core.predictor import train_service
from repro.serve import (FAIL_TOKENS, PlaneBundle, ResourceVector,
                         ServeConfig, ServePipeline,
                         ShardedServeConfig, ShardedServePipeline,
                         chassis_to_shard, device_state, featurize_batch,
                         place_batch, place_group_sharded,
                         remove_sharded, rho_pool_from_budget,
                         route_shard, shard_mesh, shard_state,
                         shard_table, unshard_state)
from repro.sim.telemetry import arrival_batch, generate_population

#: Policies the fig-7 sweep exercises through the serve backends.
POLICIES = [SchedulerPolicy(alpha=0.8),
            SchedulerPolicy(alpha=0.0),
            SchedulerPolicy(alpha=0.8, packing_weight=0.0),
            SchedulerPolicy(use_power_rule=False)]


def _loaded_state(seed, n_servers=48, per_chassis=4, cores=40, n=120):
    rng = np.random.default_rng(seed)
    st = ClusterState(n_servers=n_servers, cores_per_server=cores,
                      chassis_of_server=np.arange(n_servers) // per_chassis,
                      n_chassis=n_servers // per_chassis)
    for _ in range(n):
        srv = int(rng.integers(0, n_servers))
        c = int(rng.integers(1, 8))
        if st.free_cores[srv] >= c:
            st.place(srv, c, float(rng.uniform(0, 1)),
                     bool(rng.random() < 0.5))
    return st


def _batch(seed, b=32):
    rng = np.random.default_rng(seed)
    return (rng.choice([1, 2, 4, 8], b).astype(np.float64),
            rng.random(b) < 0.4, rng.uniform(0.05, 1.0, b),
            np.ones(b, bool))


# --- layout ---------------------------------------------------------------

def test_chassis_to_shard_contiguous_blocks():
    m = chassis_to_shard(12, 4)
    np.testing.assert_array_equal(m, np.repeat(np.arange(4), 3))
    with pytest.raises(ValueError):
        chassis_to_shard(12, 5)


def test_route_shard_rounds_are_bijections():
    b, n = 64, 4
    home = route_shard(b, n)
    np.testing.assert_array_equal(home, np.arange(b) % n)
    for rnd in range(n):
        t = route_shard(b, n, rnd)
        # each round moves every home shard to a distinct target, so
        # per-shard load stays exactly b/n and slots cannot overflow
        assert all(len(set(t[home == h])) == 1 for h in range(n))
        assert len(set((t[home == h][0] for h in range(n)))) == n


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_shard_unshard_roundtrip(n_shards):
    dst = device_state(_loaded_state(0))
    back = unshard_state(shard_state(dst, n_shards))
    for a, b in zip(back, dst):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rho_pool_from_budget_matches_power_model():
    from repro.core.power_model import F_MAX, idle_power
    from repro.core.power_model import ServerPowerModel
    m = ServerPowerModel()
    w = 48 * float(idle_power(F_MAX)) + m.p_dyn_per_core * 37.5
    assert rho_pool_from_budget(w, 48, m) == pytest.approx(37.5)
    assert np.isinf(rho_pool_from_budget(None, 48))


# --- 1-shard decision identity --------------------------------------------

@pytest.mark.parametrize("policy", POLICIES)
def test_one_shard_identical_to_place_batch_x64(policy):
    """The sharded protocol with one shard must reproduce the unsharded
    scan decision-for-decision (the same configs the fig-7 serve
    equivalence suite uses), including the final state."""
    st = _loaded_state(3, n_servers=36, per_chassis=12, n=200)
    cores, uf, p95, valid = _batch(7, 48)
    with jax.enable_x64(True):
        dst, srvs = place_batch(device_state(st, jnp.float64), cores,
                                uf, p95, valid,
                                np.full(st.n_chassis, np.inf), policy,
                                st.cores_per_server)
        want = [int(x) for x in np.asarray(srvs)]
        shd = shard_state(device_state(st, jnp.float64), 1)
        shd, got, info = place_group_sharded(shd, cores, uf, p95, valid,
                                             policy,
                                             st.cores_per_server)
        back = unshard_state(shd)
        np.testing.assert_array_equal(np.asarray(back.free_cores),
                                      np.asarray(dst.free_cores))
        np.testing.assert_array_equal(np.asarray(back.rho_peak),
                                      np.asarray(dst.rho_peak))
    assert list(got) == want
    assert info["spilled"] == 0


def test_one_shard_sim_backend_reproduces_event_oracle():
    """backend='serve-sharded' at 1 shard == backend='serve' == the
    event-driven oracle on the fig-7 cluster, trace-for-trace."""
    from repro.sim.scheduler_sim import (PredictionChannel,
                                         ServeBackendSpec, SimSpec,
                                         simulate)
    tr_e, tr_s, tr_sh = [], [], []
    e = simulate(SchedulerPolicy(alpha=0.8), PredictionChannel("ml"),
                 SimSpec(days=0.6, seed=0), trace=tr_e)
    simulate(SchedulerPolicy(alpha=0.8), PredictionChannel("ml"),
             SimSpec(days=0.6, seed=0,
                     serve=ServeBackendSpec(backend="serve")),
             trace=tr_s)
    sh = simulate(SchedulerPolicy(alpha=0.8), PredictionChannel("ml"),
                  SimSpec(days=0.6, seed=0,
                          serve=ServeBackendSpec(
                              backend="serve-sharded", shards=1)),
                  trace=tr_sh)
    assert tr_e == tr_s == tr_sh
    assert e.failure_rate == sh.failure_rate
    assert e.empty_server_ratio == sh.empty_server_ratio


def test_one_shard_pipeline_identical_to_unsharded(serve_world):
    svc, hist, labels, arrivals = serve_world
    kw = dict(n_servers=48, cores_per_server=40, blades_per_chassis=12)
    base = ServePipeline.from_history(
        svc, hist, labels, config=ServeConfig(batch_size=32), **kw)
    shp = ShardedServePipeline.from_history(
        svc, hist, labels,
        config=ShardedServeConfig(batch_size=32, n_shards=1), **kw)
    b = arrival_batch(arrivals, np.arange(64))
    r0, r1 = base.serve(b), shp.serve(b)
    np.testing.assert_array_equal(r0.server, r1.server)
    np.testing.assert_array_equal(r0.workload_type, r1.workload_type)


# --- N-shard invariants ---------------------------------------------------

def test_global_watt_budget_never_exceeded():
    """With 4 shards and a deliberately tiny global pool, the sum of
    admitted p95*cores must stay under the pool however spillover
    shuffles arrivals, and the shortfall must be reported as
    FAIL_TOKENS."""
    st = _loaded_state(1)
    cores, uf, p95, valid = _batch(2, 64)
    pool_total = 15.0
    with jax.enable_x64(True):
        shd = shard_state(device_state(st, jnp.float64), 4,
                          pool_total=pool_total)
        shd, got, _ = place_group_sharded(shd, cores, uf, p95, valid,
                                          SchedulerPolicy(alpha=0.8),
                                          st.cores_per_server)
    used = (p95 * cores)[got >= 0].sum()
    assert used <= pool_total + 1e-9
    assert (got == FAIL_TOKENS).any()
    # the pool balance accounts exactly for what was admitted (the
    # watts axis — the unbudgeted cores/GB axes stay +inf)
    assert np.asarray(shd.pool)[:, 0].sum() == \
        pytest.approx(pool_total - used)


def test_budget_invariant_across_groups_and_departures():
    """The sim's serve-sharded backend recomputes the pool net of
    live commitments each group; across a multi-group run with
    departures the fleet never exceeds the cluster budget."""
    from repro.sim.scheduler_sim import (PredictionChannel,
                                         ServeBackendSpec, SimSpec,
                                         simulate)
    from repro.core.power_model import (F_MAX, ServerPowerModel, idle_power)
    from repro.core.resources import ResourceVector
    n_servers = 720
    budget = n_servers * float(idle_power(F_MAX)) \
        + ServerPowerModel().p_dyn_per_core * 400.0
    m = simulate(SchedulerPolicy(alpha=0.8), PredictionChannel("ml"),
                 SimSpec(days=1.0, seed=0, serve=ServeBackendSpec(
                     backend="serve-sharded", shards=4,
                     cluster_budget=ResourceVector(watts=budget))))
    # a 400-rho allowance on this arrival rate forces token rejections
    # while the invariant keeps every accepted watt under budget
    assert m.failure_rate > 0.0
    assert m.placements > 0


def test_spillover_deterministic_and_admits_cross_shard():
    """Home shard 0's chassis are pre-filled, so its arrivals must
    spill; under a fixed seed two runs agree decision-for-decision and
    spilled arrivals land on foreign shards."""
    def build():
        st = _loaded_state(0, n_servers=48, per_chassis=4, n=0)
        for srv in range(12):            # shard 0 owns servers 0-11
            st.place(srv, 40, 0.5, True)
        return st
    cores, uf, p95, valid = _batch(5, 32)
    policy = SchedulerPolicy(alpha=0.8)
    outs = []
    for _ in range(2):
        shd = shard_state(device_state(build()), 4)
        shd, got, info = place_group_sharded(shd, cores, uf, p95,
                                             valid, policy, 40)
        outs.append((got, info))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    # info carries the (R,) per-resource draw — compare it per key
    for k, v in outs[0][1].items():
        np.testing.assert_array_equal(v, outs[1][1][k], err_msg=k)
    assert outs[0][1]["spilled"] > 0
    assert outs[0][1]["spill_admitted"] > 0
    # shard 0's home arrivals (indices 0 mod 4) were admitted elsewhere
    home0 = outs[0][0][route_shard(32, 4) == 0]
    assert (home0[home0 >= 0] >= 12).all()


def test_spillover_reaches_any_feasible_server():
    """Sharding must not invent capacity failures: when exactly one
    server fleet-wide can host an arrival, the spillover rounds find
    it regardless of the arrival's home shard."""
    st = _loaded_state(0, n_servers=16, per_chassis=4, n=0)
    for srv in range(16):
        # server 13 keeps 10 free cores (room for exactly one 8-core
        # arrival); everywhere else 2 free
        st.place(srv, 30 if srv == 13 else 38, 0.5, True)
    cores = np.full(4, 8.0)
    uf = np.ones(4, bool)
    p95 = np.full(4, 0.5)
    shd = shard_state(device_state(st), 4)
    shd, got, info = place_group_sharded(
        shd, cores, uf, p95, np.ones(4, bool),
        SchedulerPolicy(alpha=0.8), 40)
    assert (got == 13).sum() == 1        # exactly one winner
    assert (got < 0).sum() == 3          # the rest genuinely don't fit


def test_four_shard_failure_rate_tracks_oracle():
    """Objective regret, not feasibility regret: on the fig-7 cluster
    an unbudgeted 4-shard run must not inflate deployment failures
    relative to the event oracle."""
    from repro.sim.scheduler_sim import (PredictionChannel,
                                         ServeBackendSpec, SimSpec,
                                         simulate)
    e = simulate(SchedulerPolicy(alpha=0.8), PredictionChannel("ml"),
                 SimSpec(days=0.6, seed=0))
    s4 = simulate(SchedulerPolicy(alpha=0.8), PredictionChannel("ml"),
                  SimSpec(days=0.6, seed=0,
                          serve=ServeBackendSpec(
                              backend="serve-sharded", shards=4)))
    assert abs(s4.failure_rate - e.failure_rate) <= 0.02


def test_remove_sharded_roundtrip_restores_state_and_pool():
    st = _loaded_state(6)
    pool_total = 200.0
    with jax.enable_x64(True):
        shd0 = shard_state(device_state(st, jnp.float64), 4,
                           pool_total=pool_total)
        cores, uf, p95, valid = _batch(9, 16)
        shd, got, _ = place_group_sharded(shd0, cores, uf, p95, valid,
                                          SchedulerPolicy(alpha=0.8),
                                          st.cores_per_server)
        shd = remove_sharded(shd, got, cores, p95, uf)
        # scatter-add removal may reassociate sums of co-located VMs;
        # exactness is to the last ulp, not bitwise
        for a, b in zip(unshard_state(shd), unshard_state(shd0)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-9)
        np.testing.assert_allclose(np.asarray(shd.pool)[:, 0].sum(),
                                   pool_total)


# --- sharded pipeline -----------------------------------------------------

@pytest.fixture(scope="module")
def serve_world():
    pop = generate_population(500, seed=0)
    hist, arrivals = F.split_history_arrivals(pop)
    labels = hist.labels.astype(np.float64)
    aggs = F.subscription_aggregates(hist, labels)
    svc = train_service(F.build_features(hist, aggs),
                        labels.astype(np.int64),
                        F.p95_bucket([v.p95_util for v in hist.vms]),
                        n_trees=12)
    return svc, hist, labels, arrivals


def test_sharded_pipeline_end_to_end(serve_world):
    svc, hist, labels, arrivals = serve_world
    pipe = ShardedServePipeline.from_history(
        svc, hist, labels, n_servers=48, cores_per_server=40,
        blades_per_chassis=12,
        config=ShardedServeConfig(
            batch_size=32, n_shards=4,
            planes=PlaneBundle(cluster_budget=ResourceVector(
                watts=48 * 112.0 + 800.0))))
    b = arrival_batch(arrivals, np.arange(96))
    res = pipe.serve(b)
    assert len(res.server) == 96
    assert res.n_admitted + res.n_capacity_rejected \
        + res.n_power_rejected + res.n_token_rejected == 96
    # token accounting: pool spent == admitted rho, across all shards
    pool0 = rho_pool_from_budget(48 * 112.0 + 800.0, 48,
                                 pipe.power_model)
    rho = float(np.asarray(pipe.global_state().rho_peak).sum())
    assert rho <= pool0 + 1e-4
    np.testing.assert_allclose(pipe.pool_left().sum(), pool0 - rho,
                               atol=1e-4)


def test_warm_start_pipeline_nets_committed_rho(serve_world):
    """A pipeline built over a cluster with rho already committed must
    seed its token pool with the *remaining* allowance, so warm starts
    cannot admit a full budget on top of existing load."""
    from repro.core.placement import ClusterState
    from repro.serve.featurizer import table_from_history
    svc, hist, labels, _ = serve_world
    st = ClusterState(n_servers=48, cores_per_server=40,
                      chassis_of_server=np.arange(48) // 12, n_chassis=4)
    st.place(0, 20, 0.9, True)            # 18 rho-units pre-committed
    budget_w = 48 * 112.0 + 800.0
    cap = max(v.subscription for v in hist.vms) + 8
    pipe = ShardedServePipeline(
        svc, table_from_history(hist, labels, cap), device_state(st),
        cores_per_server=40, blades_per_chassis=12,
        config=ShardedServeConfig(
            batch_size=32, n_shards=4,
            planes=PlaneBundle(
                cluster_budget=ResourceVector(watts=budget_w))))
    pool = rho_pool_from_budget(budget_w, 48, pipe.power_model)
    np.testing.assert_allclose(pipe.pool_left().sum(), pool - 18.0,
                               rtol=1e-5)


def test_sharded_batch_size_must_divide(serve_world):
    svc, hist, labels, _ = serve_world
    with pytest.raises(ValueError):
        ShardedServePipeline.from_history(
            svc, hist, labels, n_servers=48, cores_per_server=40,
            blades_per_chassis=12,
            config=ShardedServeConfig(batch_size=30, n_shards=4))
    st = _loaded_state(0)
    with pytest.raises(ValueError):
        place_group_sharded(shard_state(device_state(st), 4),
                            *_batch(0, 30), SchedulerPolicy(), 40)


# --- shard_map execution (needs a multi-device runtime) -------------------

needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")


@needs_devices
def test_shard_map_matches_vmap():
    """The mesh execution must agree with the single-device vmap twin
    decision-for-decision (identical per-shard arithmetic)."""
    st = _loaded_state(2)
    cores, uf, p95, valid = _batch(3, 32)
    policy = SchedulerPolicy(alpha=0.8)
    outs = []
    for mesh in (None, shard_mesh(4)):
        shd = shard_state(device_state(st), 4, pool_total=120.0)
        shd, got, info = place_group_sharded(shd, cores, uf, p95,
                                             valid, policy, 40,
                                             mesh=mesh)
        outs.append((got, np.asarray(shd.pool)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_allclose(outs[0][1], outs[1][1], rtol=1e-6)


@needs_devices
def test_sharded_pipeline_on_mesh(serve_world):
    svc, hist, labels, arrivals = serve_world
    pipe = ShardedServePipeline.from_history(
        svc, hist, labels, n_servers=48, cores_per_server=40,
        blades_per_chassis=12,
        config=ShardedServeConfig(batch_size=32, n_shards=4,
                                  use_shard_map=True))
    assert pipe.mesh is not None
    res = pipe.serve(arrival_batch(arrivals, np.arange(64)))
    assert res.n_admitted > 0


@needs_devices
def test_shard_table_featurize_parity(serve_world):
    svc, hist, labels, arrivals = serve_world
    from repro.serve import table_from_history
    cap = max(v.subscription for v in hist.vms) + 8
    table = table_from_history(hist, labels, cap)
    sharded = shard_table(table, shard_mesh(4))
    assert sharded.capacity % 4 == 0
    b = arrival_batch(arrivals, np.arange(32))
    np.testing.assert_allclose(
        np.asarray(featurize_batch(sharded, b)),
        np.asarray(featurize_batch(table, b)), atol=1e-6)
