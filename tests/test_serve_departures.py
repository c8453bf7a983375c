"""Gathered departures on the unsharded serve path.

`ServePipeline` keeps the live rows of every departure run on the host
and applies them in one `remove_batch` dispatch just before the next
read or write of the aggregates (a micro-batch's placement, a cap run,
`flush`, a `state` read). These tests hold it to the per-run path it
replaces — each run applied alone at its merged position — and to the
ordering `depart_to` promises."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import features as F
from repro.core.placement import ClusterState
from repro.core.predictor import train_service
from repro.obs import Observability
from repro.serve import (AdaptiveConfig, BallooningConfig, EmergencyConfig,
                         PlaneBundle, ServeConfig, ServePipeline,
                         device_state, placement)
from repro.serve import pipeline as pl
from repro.serve.featurizer import table_from_history
from repro.sim.telemetry import arrival_batch, generate_population

BUDGET_TIGHT = 1480.0
N_SERVERS, PER_CHASSIS, CORES = 48, 12, 40


@pytest.fixture(scope="module")
def world():
    pop = generate_population(300, seed=1)
    hist, arrivals = F.split_history_arrivals(pop)
    labels = hist.labels.astype(np.float64)
    aggs = F.subscription_aggregates(hist, labels)
    svc = train_service(F.build_features(hist, aggs),
                        labels.astype(np.int64),
                        F.p95_bucket([v.p95_util for v in hist.vms]),
                        n_trees=8)
    cap = max(v.subscription for v in hist.vms) + 8
    return svc, table_from_history(hist, labels, cap), \
        arrival_batch(arrivals)


class _PerRun(ServePipeline):
    """The path the gathered one replaces: every departure run applied
    alone, one `remove_batch` dispatch at its merged position."""

    def _apply_departures(self, servers, cores, p95_eff, is_uf,
                          mem_gb=None):
        self._flush_caps()
        self.state = placement.remove_batch(
            self.state, jnp.asarray(servers), jnp.asarray(cores),
            jnp.asarray(p95_eff), jnp.asarray(is_uf),
            mem_gb=None if mem_gb is None else jnp.asarray(mem_gb))


def _preloaded(seed=3, n=220, n_servers=N_SERVERS):
    """A part-loaded cluster and the VMs on it, as (server, cores, p95,
    is_uf, mem_gb) columns; p95 in 1/64 steps and whole GB, so every
    sum is exact in float64."""
    rng = np.random.default_rng(seed)
    chassis_of = np.arange(n_servers) // PER_CHASSIS
    st = ClusterState(n_servers=n_servers, cores_per_server=CORES,
                      chassis_of_server=chassis_of,
                      n_chassis=n_servers // PER_CHASSIS)
    vms = []
    for _ in range(n):
        srv, c = int(rng.integers(0, n_servers)), int(rng.integers(1, 8))
        if st.free_cores[srv] >= c:
            p95 = float(rng.integers(8, 64)) / 64.0
            uf = bool(rng.random() < 0.5)
            st.place(srv, c, p95, uf)
            vms.append((srv, c, p95, uf, float(rng.integers(1, 16))))
    cols = [np.array(col) for col in zip(*vms)]
    mem = np.zeros(st.n_chassis)
    mem_nuf = np.zeros(st.n_chassis)
    np.add.at(mem, chassis_of[cols[0]], cols[4])
    np.add.at(mem_nuf, chassis_of[cols[0]], cols[4] * ~cols[3])
    return st, cols, mem, mem_nuf


def _state(dtype, seed=3):
    st, _, mem, mem_nuf = _preloaded(seed)
    return device_state(st, dtype, mem_gb=mem, mem_nuf=mem_nuf)


def _pipe(world, state, cls=ServePipeline, ballooning=True, obs=None,
          hosts=2):
    svc, table, _ = world
    planes = PlaneBundle(
        emergency=EmergencyConfig.from_model(BUDGET_TIGHT),
        ballooning=BallooningConfig() if ballooning else None,
        adaptive=AdaptiveConfig() if ballooning else None, obs=obs)
    return cls(svc, table, state, cores_per_server=CORES,
               blades_per_chassis=PER_CHASSIS,
               config=ServeConfig(batch_size=32, n_ingest_hosts=hosts,
                                  planes=planes))


def _rows(batch, lo, hi):
    return type(batch)(*(getattr(batch, f)[lo:hi]
                         for f in type(batch).__dataclass_fields__))


def _depart(pipe, host, vms, idx, t, pad=0, with_mem=True):
    """Push the preloaded VMs `idx` as one departure run, with `pad`
    ignored rows (server -1) behind them."""
    srv, cores, p95, uf, mem = (np.concatenate([c[idx], np.full(pad, f)])
                                for c, f in zip(vms, (-1, 0, 0, 0, 0)))
    stamps = t + 0.01 * np.arange(len(srv))
    return pipe.depart_to(host, srv.astype(np.int32), cores, p95,
                          uf.astype(bool), t=stamps,
                          mem_gb=mem if with_mem else None)


def _drive(pipe, arrivals, vms):
    """A 2-host stream of mixed runs: arrivals dealt to both hosts,
    departure runs (padded and not, with and without memory) before
    and after power sweeps, then a flush. Returns the results and the
    number of departure runs pushed."""
    out, n, runs = [], 0, 0
    order = np.random.default_rng(11).permutation(len(vms[0]))
    take = iter(np.array_split(order[:96], 12))
    powers = ([2200.0, 1500.0, 2100.0, 1700.0],
              [2300.0, 2250.0, 1400.0, 2150.0])
    for k in range(6):
        t0 = 100.0 * k
        out += pipe.submit_to(0, _rows(arrivals, n, n + 12),
                              t=t0 + 2.0 * np.arange(12.0))
        n += 12
        out += _depart(pipe, k % 2, vms, next(take), t0 + 30.0, pad=3 * k,
                       with_mem=k != 3)
        out += pipe.submit_to(1, _rows(arrivals, n, n + 12),
                              t=t0 + 40.0 + 2.0 * np.arange(12.0))
        n += 12
        sweep = (k % 2, [0, 1, 2, 3], powers[k % 2],
                 t0 + 70.0 + np.arange(4.0))
        if k % 2:
            out += pipe.cap_to(*sweep[:3], t=sweep[3])
            out += _depart(pipe, 0, vms, next(take), t0 + 80.0)
        else:
            out += _depart(pipe, 1, vms, next(take), t0 + 65.0)
            out += pipe.cap_to(*sweep[:3], t=sweep[3])
        runs += 2
    tail = pipe.flush()
    return out + ([] if tail is None else [tail]), runs


def _planes(pipe):
    return jax.device_get((pipe.emergency, pipe.balloon_state,
                           pipe.adaptive_state))


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_gathered_stream_matches_one_dispatch_per_run(world, dtype):
    """Every plane on, two hosts: the same decisions, plane states and
    ledgers as each departure run applied alone."""
    _, _, arrivals = world
    vms = _preloaded()[1]
    with jax.enable_x64(dtype == "float64"):
        runs = {}
        for cls in (ServePipeline, _PerRun):
            pipe = _pipe(world, _state(jnp.dtype(dtype)), cls)
            res, n_runs = _drive(pipe, arrivals, vms)
            runs[cls] = (res, _planes(pipe), jax.device_get(pipe.state),
                         pipe.alarms)
    (got, got_pl, got_st, got_al), (want, want_pl, want_st, want_al) = \
        runs[ServePipeline], runs[_PerRun]
    assert len(got) == len(want) == 144 // 32 + 1
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a.server, b.server)
        np.testing.assert_array_equal(a.p95_eff, b.p95_eff)
    assert got_al == want_al > 0
    _assert_trees_equal(got_pl, want_pl)
    _assert_trees_equal(got_st, want_st)
    assert np.asarray(got_st.free_cores).dtype == np.dtype(dtype)
    assert n_runs == 12


def test_state_read_sees_every_departure_pushed(world):
    """A `state` read between pushes applies what was gathered: the
    state equals `remove_batch` of every run pushed so far, and the
    removal is dispatched at the read, once for any number of runs."""
    _, cols, mem, mem_nuf = _preloaded()
    obs = Observability.full()
    state0 = device_state(_preloaded()[0], mem_gb=mem, mem_nuf=mem_nuf)
    pipe = _pipe(world, state0, obs=obs, hosts=1)

    def removes():
        return obs.tracer.totals().get("remove", (0, 0.0))[0]

    want, t = state0, 1.0
    for step, groups in enumerate(([np.arange(5)],
                                   [np.arange(5, 9), np.arange(9, 20)],
                                   [np.arange(20, 21)])):
        for idx in groups:
            _depart(pipe, 0, cols, idx, t, pad=2)
            t += 1.0
            want = placement.remove_batch(
                want, *(jnp.asarray(c[idx]) for c in cols[:4]),
                mem_gb=jnp.asarray(cols[4][idx]))
        assert removes() == step            # gathered, not applied yet
        _assert_trees_equal(pipe.state, want)
        assert removes() == step + 1
    # a run of ignored rows alone (the padding a caller sends) gathers
    # nothing, and a read then dispatches nothing
    pipe.depart_to(0, np.full(4, -1, np.int32), np.zeros(4), np.zeros(4),
                   np.zeros(4, bool), t=t + np.arange(4.0))
    _assert_trees_equal(pipe.state, want)
    assert removes() == 3


@pytest.mark.parametrize("ballooning", [False, True],
                         ids=["queued_windows", "eager_windows"])
@pytest.mark.parametrize("first", ["depart", "cap"])
def test_cap_sweep_reads_aggregates_at_its_merged_position(world, first,
                                                           ballooning):
    """A sweep merged after a departure sees the post-departure
    aggregates; one merged before it sees the pre-departure ones —
    with its windows queued for fusion or applied eagerly."""
    _, cols, mem, mem_nuf = _preloaded()
    st = _preloaded()[0]
    pre = device_state(st, mem_gb=mem, mem_nuf=mem_nuf)
    # every VM of chassis 0 leaves: its criticality levels and NUF
    # memory, which the sweep apportions by, change
    idx = np.flatnonzero(cols[0] // PER_CHASSIS == 0)
    post = placement.remove_batch(pre, *(jnp.asarray(c[idx])
                                         for c in cols[:4]),
                                  mem_gb=jnp.asarray(cols[4][idx]))
    power = [2300.0, 1500.0, 2100.0, 1700.0]

    def sweep_from(state, depart_at=None):
        pipe = _pipe(world, state, ballooning=ballooning, hosts=1)
        if depart_at == "before":
            _depart(pipe, 0, cols, idx, 1.0)
        pipe.cap_to(0, [0, 1, 2, 3], power, t=10.0 + np.arange(4.0))
        if depart_at == "after":
            _depart(pipe, 0, cols, idx, 20.0)
        pipe.flush()
        return _planes(pipe)

    saw_pre, saw_post = sweep_from(pre), sweep_from(post)
    assert not np.array_equal(np.asarray(saw_pre[0].pstate),
                              np.asarray(saw_post[0].pstate))
    got = sweep_from(pre, "before" if first == "depart" else "after")
    _assert_trees_equal(got, saw_post if first == "depart" else saw_pre)


def test_remove_dispatches_per_batch(world):
    """Each served batch carries at most one gathered removal, nested
    in its ``depart`` span; beyond that only a sweep (nested in
    ``cap``) or the final flush applies one — fewer dispatches than
    departure runs."""
    _, _, arrivals = world
    obs = Observability.full()
    pipe = _pipe(world, _state(jnp.float32), obs=obs)
    _, runs = _drive(pipe, arrivals, _preloaded()[1])
    rows = obs.tracer.tail(len(obs.tracer))
    name = dict(zip(rows["seq"].tolist(), rows["name"].tolist()))
    rm = rows[rows["name"] == "remove"]
    parents = [name[p] for p in rm["parent"].tolist()]
    assert set(parents) <= {"depart", "cap"}
    in_batch = rm["batch"][np.array(parents) == "depart"]
    batches = obs.registry.value("serve_batches_total")
    sweeps = int((rows["name"] == "cap").sum())
    assert np.bincount(in_batch[in_batch > 0]).max() <= 1
    assert len(rm) <= batches + sweeps + 1
    assert 0 < len(rm) < runs


def test_gathered_removal_compiles_once_per_ladder_size(world):
    """Building a pipeline compiles the gathered removal at most once
    per ladder size; any buffer, up to several ladder tops, then runs
    without a compile and equals one `remove_batch` of its rows."""
    n_servers = 60             # a shape no other test of the file uses
    chassis_of = np.arange(n_servers) // PER_CHASSIS
    st = ClusterState(n_servers=n_servers, cores_per_server=CORES,
                      chassis_of_server=chassis_of,
                      n_chassis=n_servers // PER_CHASSIS)
    rng = np.random.default_rng(5)
    with jax.enable_x64(True):
        state0 = device_state(st, jnp.float64)
        size0 = pl._remove_gathered._cache_size()
        pipe = _pipe(world, state0, hosts=1)
        built = pl._remove_gathered._cache_size()
        assert built - size0 <= len(pl.DEPART_LADDER)
        want, t = state0, 1.0
        for n in (1, 100, 300, 2 * pl.DEPART_LADDER[-1] + 7):
            cols = (rng.integers(-1, n_servers, n).astype(np.int32),
                    rng.integers(1, 8, n).astype(np.float64),
                    rng.integers(1, 64, n) / 64.0, rng.random(n) < 0.5,
                    rng.integers(0, 16, n).astype(np.float64))
            pipe.depart(*cols[:4], mem_gb=cols[4])
            want = placement.remove_batch(
                want, *(jnp.asarray(c) for c in cols[:4]),
                mem_gb=jnp.asarray(cols[4]))
            _assert_trees_equal(pipe.state, want)
        assert pl._remove_gathered._cache_size() == built
