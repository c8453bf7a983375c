"""Property-based hardening of the serve-plane invariants.

Three families, each against an independent oracle, driven by
hypothesis (the real package when installed, else the deterministic
stub `tests/_hypothesis_stub.py` — the suite must pass under both):

  * `serve.ingest.kway_merge` == an ``np.lexsort`` of the
    concatenated ``(t, host, seq)`` keys, for ragged per-host streams
    with duplicates and empty hosts;
  * the scatter-free rank-maintenance permutation
    (`serve.placement._compose_inverse`) stays a valid bijection and
    equals a literal delete-then-insert list oracle; end to end, the
    incrementally-maintained order keeps reproducing the from-scratch
    sequential rule under arrival/departure/migration interleavings;
  * the sharded power-token pools conserve through randomized
    cap -> arrive -> depart -> adapt sequences: free pools never go
    negative, committed rho is never revoked by a controller
    back-off, and each adaptive retarget lands exactly on
    ``max(base * ratio - committed, 0)``.
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.placement import ClusterState, SchedulerPolicy
from repro.serve import (AdaptiveConfig, EmergencyConfig, PlaneBundle,
                         ResourceVector,
                         ShardedServeConfig, ShardedServePipeline,
                         device_state, kway_merge, place_batch,
                         remove_batch)
from repro.serve.placement import _compose_inverse

# --- kway_merge vs the lexsort oracle -------------------------------------


@st.composite
def ragged_streams(draw):
    """1-5 hosts, each a sorted stamp array of 0-12 events drawn from
    a small value set (cross-host duplicates are likely — exactly the
    tie territory the merge contract pins down)."""
    n_hosts = draw(st.integers(min_value=1, max_value=5))
    streams = []
    for _ in range(n_hosts):
        vals = draw(st.lists(st.integers(min_value=0, max_value=30),
                             min_size=0, max_size=12))
        streams.append(np.sort(np.asarray(vals, np.float64)) * 0.5)
    return streams


@settings(max_examples=50, deadline=None)
@given(ragged_streams())
def test_kway_merge_matches_lexsort_oracle(streams):
    host, idx = kway_merge(streams)
    ts = np.concatenate(streams) if streams else np.empty(0)
    hosts = np.concatenate([np.full(len(s), h, np.int32)
                            for h, s in enumerate(streams)])
    seqs = np.concatenate([np.arange(len(s), dtype=np.int64)
                           for s in streams])
    order = np.lexsort((seqs, hosts, ts))
    np.testing.assert_array_equal(np.asarray(host), hosts[order])
    np.testing.assert_array_equal(np.asarray(idx), seqs[order])


# --- rank-maintenance permutation bijection -------------------------------


def _compose_oracle(perm_row, fresh_row, dold_row, delta):
    """Literal delete-then-insert: drop the moved servers from their
    vacated positions, pin them at their landing positions, stream
    the survivors (old relative order) through the gaps."""
    vacated = set(int(p) for p in dold_row)
    survivors = [s for pos, s in enumerate(perm_row)
                 if pos not in vacated]
    out = [-1] * len(perm_row)
    for f, d in zip(fresh_row, delta):
        out[int(f)] = int(d)
    it = iter(survivors)
    for q in range(len(out)):
        if out[q] < 0:
            out[q] = int(next(it))
    return out


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_compose_inverse_is_the_delete_insert_bijection(seed):
    rng = np.random.default_rng(seed)
    S = int(rng.integers(4, 25))
    K = int(rng.integers(1, min(S, 7)))
    R = int(rng.integers(1, 4))
    perm = np.stack([rng.permutation(S) for _ in range(R)]) \
        .astype(np.int32)
    delta = rng.choice(S, K, replace=False).astype(np.int32)
    pos_of = np.argsort(perm, axis=-1)                  # server -> pos
    d_old = pos_of[:, delta].astype(np.int32)
    fresh = np.stack([rng.choice(S, K, replace=False)
                      for _ in range(R)]).astype(np.int32)
    got = np.asarray(_compose_inverse(jnp.asarray(perm),
                                      jnp.asarray(fresh),
                                      jnp.asarray(d_old),
                                      jnp.asarray(delta)))
    for r in range(R):
        want = _compose_oracle(perm[r], fresh[r], d_old[r], delta)
        np.testing.assert_array_equal(got[r], want)
        # and it IS a bijection: every server exactly once
        np.testing.assert_array_equal(np.sort(got[r]), np.arange(S))


def _fresh_cluster(n_servers=24, per_chassis=4, cores=40):
    return ClusterState(
        n_servers=n_servers, cores_per_server=cores,
        chassis_of_server=np.arange(n_servers) // per_chassis,
        n_chassis=n_servers // per_chassis)


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_rank_order_survives_random_interleavings(seed):
    """Randomized arrival/departure/migration rounds: the maintained
    permutation must keep producing the sequential from-scratch
    oracle's decision on every arrival (fixed shapes, so the jit
    caches across examples)."""
    rng = np.random.default_rng(seed)
    policy = SchedulerPolicy(alpha=0.8)
    st_np = _fresh_cluster()
    B = 12
    placed: list = []
    with jax.enable_x64(True):
        dst = device_state(copy.deepcopy(st_np), jnp.float64)
        for _ in range(3):
            cores = rng.choice([1, 2, 4, 8], B).astype(np.float64)
            is_uf = rng.random(B) < 0.5
            p95 = rng.uniform(0.05, 1.0, B)
            want = []
            for i in range(B):
                s = policy.choose(st_np, int(cores[i]), bool(is_uf[i]))
                want.append(-1 if s is None else s)
                if s is not None:
                    st_np.place(s, int(cores[i]), float(p95[i]),
                                bool(is_uf[i]))
                    placed.append((s, cores[i], p95[i], is_uf[i]))
            dst, srvs = place_batch(dst, cores, is_uf, p95,
                                    np.ones(B, bool),
                                    np.full(st_np.n_chassis, np.inf),
                                    policy, st_np.cores_per_server)
            assert [int(x) for x in np.asarray(srvs)] == want
            if not placed:
                continue
            k = int(rng.integers(0, len(placed) // 2 + 1))
            if k == 0:
                continue
            pick = sorted(rng.choice(len(placed), k, replace=False)
                          .tolist())
            dep = [placed[j] for j in pick]
            placed = [p for j, p in enumerate(placed)
                      if j not in set(pick)]
            for s, c, p, u in dep:
                st_np.remove(int(s), float(c), float(p), bool(u))
            dst = remove_batch(
                dst, jnp.asarray([d[0] for d in dep], jnp.int32),
                jnp.asarray([d[1] for d in dep]),
                jnp.asarray([d[2] for d in dep]),
                jnp.asarray([bool(d[3]) for d in dep]))
        np.testing.assert_array_equal(np.asarray(dst.free_cores),
                                      st_np.free_cores)


# --- token-pool conservation under cap/depart/adapt -----------------------


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


def _pool_invariants(pipe):
    """The conservation triple after an adaptive retarget: free >= 0,
    and free == max(base * ratio - committed, 0) per shard — checked
    per resource axis (the controller's ratio scales watts only; the
    unbudgeted +inf axes are vacuously conserved)."""
    free = np.asarray(pipe.sharded.pool)                  # (N, R)
    committed = np.asarray(pipe.sharded.shards.res_peak).sum(1)
    base = np.asarray(pipe._pool_base)                    # (N, R)
    ratio = np.asarray(pipe.adaptive_ratio, np.float64)   # (N,)
    mult = np.column_stack(
        [ratio, np.ones_like(ratio), np.ones_like(ratio)])
    assert (free >= 0).all()
    finite = np.isfinite(base)
    np.testing.assert_allclose(
        free[finite],
        np.maximum(base * mult - committed, 0)[finite], rtol=1e-5,
        atol=1e-4)
    return committed[:, 0]


def test_token_pools_conserved_through_random_sequences(serve_world):
    """Randomized cap -> arrive -> depart -> adapt interleavings on a
    4-shard pipeline with both planes live: after every cap scan the
    pools sit exactly on the retarget formula, committed rho is only
    ever moved by placements/departures (never by the controller),
    and no pool goes negative. Sequences come from the seeded
    generator (fixed shapes keep the jit cache warm across runs)."""
    from repro.sim.telemetry import arrival_batch
    svc, hist, labels, arrivals = serve_world
    for seed in range(4):
        rng = np.random.default_rng(seed)
        acfg = AdaptiveConfig(window=8, min_history=2, ratio_max=3.0)
        pipe = ShardedServePipeline.from_history(
            svc, hist, labels, n_servers=48, cores_per_server=40,
            blades_per_chassis=12,
            config=ShardedServeConfig(
                batch_size=32, n_shards=4,
                planes=PlaneBundle(
                    adaptive=acfg,
                    emergency=EmergencyConfig.from_model(1860.0),
                    cluster_budget=ResourceVector(watts=40000.0))))
        t = 1.0
        placed: list = []
        idx = np.arange(4)
        for _ in range(8):
            op = rng.choice(["cap_cool", "cap_hot", "arrive", "depart"])
            if op.startswith("cap"):
                pw = np.full(4, 500.0 if op == "cap_cool" else 6000.0)
                pipe.cap_to(0, idx, pw, t=t + (idx + 1) * 1e-7)
                t += 1.0
                pipe.flush()
                before = np.asarray(
                    pipe.sharded.shards.rho_peak).sum()
                committed = _pool_invariants(pipe)
                # a cap scan must not move committed rho at all
                np.testing.assert_allclose(committed.sum(), before)
            elif op == "arrive":
                lo = int(rng.integers(0, len(arrivals.vms) - 32))
                b = arrival_batch(arrivals, np.arange(lo, lo + 32))
                r = pipe.serve(b)           # queue-bypassing sync path
                srv = np.asarray(r.server)
                for i in np.flatnonzero(srv >= 0):
                    placed.append((int(srv[i]), float(b.cores[i]),
                                   float(r.p95_eff[i]),
                                   bool(r.workload_type[i])))
            elif op == "depart" and placed:
                k = int(rng.integers(1, min(len(placed), 8) + 1))
                pick = sorted(rng.choice(len(placed), k, replace=False)
                              .tolist())
                dep = [placed[j] for j in pick]
                placed = [p for j, p in enumerate(placed)
                          if j not in set(pick)]
                for s, c, p, u in dep:
                    pipe.depart_to(0, np.array([s]), np.array([c]),
                                   np.array([p]), np.array([u]),
                                   t=np.array([t]))
                    t += 1e-3
                t += 1.0
                pipe.flush()
        pipe.flush()
        assert (np.asarray(pipe.sharded.pool) >= 0).all()
