"""Scatter-free rank maintenance (`serve.placement`) vs the argsort
oracle (DESIGN.md §13).

The batched placement scan keeps the rank-rule order as a permutation
maintained by binary-search landing positions + a closed-form
histogram compose — never an S-sized scatter and never a re-sort. The
oracle is `SchedulerPolicy.choose` stepped one arrival at a time,
which recomputes the full argsort-based rank weighting from scratch on
every call: any drift in the incremental permutation (a missed rank
delta, a stale key after a departure, a broken tie) shows up as a
decision mismatch. Everything runs under x64 where the scan is
bit-equivalent to the numpy rule, so equality is exact — no tolerance
hides an off-by-one rank."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.placement import ClusterState, SchedulerPolicy
from repro.serve import device_state, place_batch, remove_batch


def _fresh(n_servers, per_chassis, cores):
    return ClusterState(
        n_servers=n_servers, cores_per_server=cores,
        chassis_of_server=np.arange(n_servers) // per_chassis,
        n_chassis=n_servers // per_chassis)


def _oracle_round(st_np, policy, cores, is_uf, p95):
    """Sequential choose+place — the from-scratch argsort oracle."""
    want = []
    for i in range(len(cores)):
        s = policy.choose(st_np, int(cores[i]), bool(is_uf[i]))
        want.append(-1 if s is None else s)
        if s is not None:
            st_np.place(s, int(cores[i]), float(p95[i]), bool(is_uf[i]))
    return want


def _device_round(dst, policy, cores, is_uf, p95, cps, n_chassis):
    dst, srvs = place_batch(dst, cores, is_uf, p95,
                            np.ones(len(cores), bool),
                            np.full(n_chassis, np.inf), policy, cps)
    return dst, [int(x) for x in np.asarray(srvs)]


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_interleaved_arrivals_departures_migrations(seed):
    """Property: across rounds of place / depart / migrate (a departed
    VM's spec re-arrives next round), every decision equals the
    sequential oracle and the final aggregates match exactly."""
    rng = np.random.default_rng(seed)
    policy = SchedulerPolicy(alpha=0.8)
    st_np = _fresh(36, 12, 40)
    B = 24
    placed: list[tuple] = []
    migrants: list[tuple] = []
    with jax.enable_x64(True):
        dst = device_state(copy.deepcopy(st_np), jnp.float64)
        for _ in range(5):
            n_new = B - len(migrants)
            cores = np.concatenate([
                np.array([m[1] for m in migrants], np.float64),
                rng.choice([1, 2, 4, 8, 16], n_new).astype(np.float64)])
            is_uf = np.concatenate([
                np.array([m[3] for m in migrants], bool),
                rng.random(n_new) < 0.5])
            p95 = np.concatenate([
                np.array([m[2] for m in migrants], np.float64),
                rng.uniform(0.05, 1.0, n_new)])
            migrants = []
            want = _oracle_round(st_np, policy, cores, is_uf, p95)
            dst, got = _device_round(dst, policy, cores, is_uf, p95,
                                     st_np.cores_per_server,
                                     st_np.n_chassis)
            assert got == want
            placed += [(s, cores[i], p95[i], is_uf[i])
                       for i, s in enumerate(want) if s >= 0]
            if not placed:
                continue
            k = int(rng.integers(1, max(len(placed) // 3, 2)))
            pick = set(rng.choice(len(placed), size=min(k, len(placed)),
                                  replace=False).tolist())
            dep = [placed[j] for j in sorted(pick)]
            placed = [p for j, p in enumerate(placed) if j not in pick]
            # half the departures come back as migrations next round
            migrants = dep[: len(dep) // 2]
            for s, c, p, u in dep:
                st_np.remove(int(s), int(c), float(p), bool(u))
            dst = remove_batch(
                dst, jnp.asarray([d[0] for d in dep], jnp.int32),
                jnp.asarray([d[1] for d in dep]),
                jnp.asarray([d[2] for d in dep]),
                jnp.asarray([bool(d[3]) for d in dep]))
        np.testing.assert_array_equal(np.asarray(dst.free_cores),
                                      st_np.free_cores)
        np.testing.assert_allclose(np.asarray(dst.rho_peak),
                                   st_np.rho_peak, rtol=0, atol=0)


def test_rank_ties_identical_arrivals():
    """Edge: an empty cluster + identical arrivals makes every server
    key tie — placement must break ties exactly like the oracle's
    stable argsort, arrival after arrival."""
    policy = SchedulerPolicy(alpha=0.8)
    st_np = _fresh(24, 4, 40)
    B = 16
    cores = np.full(B, 2.0)
    p95 = np.full(B, 0.5)
    with jax.enable_x64(True):
        dst = device_state(copy.deepcopy(st_np), jnp.float64)
        for is_uf in (np.ones(B, bool),
                      np.arange(B) % 2 == 0):    # mixed-type tie round
            want = _oracle_round(st_np, policy, cores, is_uf, p95)
            dst, got = _device_round(dst, policy, cores, is_uf, p95,
                                     st_np.cores_per_server,
                                     st_np.n_chassis)
            assert got == want


def test_full_servers_fail_then_reopen():
    """Edge: filling every server drives the infeasible path (all
    FAIL codes, permutation must survive a zero-feasible batch), then
    departures reopen capacity and ranks must be consistent again."""
    policy = SchedulerPolicy(alpha=0.8)
    st_np = _fresh(4, 2, 8)
    with jax.enable_x64(True):
        dst = device_state(copy.deepcopy(st_np), jnp.float64)
        cores = np.full(6, 8.0)
        is_uf = np.array([True, False, True, False, True, False])
        p95 = np.full(6, 0.6)
        want = _oracle_round(st_np, policy, cores, is_uf, p95)
        dst, got = _device_round(dst, policy, cores, is_uf, p95,
                                 st_np.cores_per_server, st_np.n_chassis)
        assert got == want
        assert want[4:] == [-1, -1]         # cluster exactly full
        # free two servers, then place into the reopened capacity
        for s in (want[1], want[2]):
            st_np.remove(int(s), 8, 0.6, bool(is_uf[want.index(s)]))
        dep = np.array([want[1], want[2]], np.int32)
        dst = remove_batch(dst, jnp.asarray(dep),
                           jnp.asarray([8.0, 8.0]),
                           jnp.asarray([0.6, 0.6]),
                           jnp.asarray([is_uf[want.index(int(d))]
                                        for d in dep]))
        cores2 = np.array([4.0, 4.0, 8.0, 8.0])
        is_uf2 = np.array([True, True, False, False])
        p952 = np.array([0.3, 0.9, 0.5, 0.5])
        want2 = _oracle_round(st_np, policy, cores2, is_uf2, p952)
        dst, got2 = _device_round(dst, policy, cores2, is_uf2, p952,
                                  st_np.cores_per_server,
                                  st_np.n_chassis)
        assert got2 == want2
        np.testing.assert_array_equal(np.asarray(dst.free_cores),
                                      st_np.free_cores)
