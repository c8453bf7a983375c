"""Fleet observability plane (repro.obs, DESIGN.md §14).

Covers the three pillars and their acceptance invariants:

  * registry / audit / tracer unit behavior (kinds, labels, rings,
    exporters);
  * metrics-on is decision-bit-identical to metrics-off, unsharded
    and sharded — the kernels gained outputs, never inputs;
  * counters reconcile against oracle totals: admits + fails ==
    arrivals (exact integers), sweep counters == the standalone
    kernel's outputs, tokens drawn − credited == the pool delta, and
    the sim exporter reproduces `SimMetrics` exactly;
  * the `SimMetrics.throttled_s` array and its legacy scalar
    properties agree with the emergency plane's level order.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import features as F
from repro.core.placement import ClusterState, SchedulerPolicy
from repro.core.predictor import train_service
from repro.obs import (AuditTrail, LEVEL_NAMES, MetricsRegistry,
                       Observability, SpanTracer, record_sim_metrics)
from repro.obs.tracing import OUTERMOST
from repro.serve import (CRIT_NUF, CRIT_UF, AdaptiveConfig,
                         BallooningConfig, EmergencyConfig,
                         PlaneBundle, ResourceVector,
                         ServeConfig, ServePipeline, ShardedServeConfig,
                         ShardedServePipeline, device_state, emergency)
from repro.serve.featurizer import table_from_history
from repro.sim.telemetry import arrival_batch, generate_population

BUDGET_TIGHT = 1480.0


# -- registry ---------------------------------------------------------------
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("hits_total", help="hits")
    c.inc()
    c.inc(2.5)
    assert reg.value("hits_total") == 3.5
    with pytest.raises(ValueError):
        c.inc(-1.0)
    with pytest.raises(ValueError):
        c.inc(float("nan"))
    g = reg.gauge("level")
    g.set(4.0)
    g.dec(1.5)
    assert reg.value("level") == 2.5
    h = reg.histogram("lat_seconds", lo=1e-6, base=2.0, n_buckets=40)
    for v in (1e-6, 3e-6, 0.5, 0.5, 2.0):
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(1e-6 + 3e-6 + 3.0)
    assert h.quantile(0.5) <= 1.0       # bucket bound above the median
    assert h.quantile(1.0) >= 2.0


def test_registry_labels_and_kind_mismatch():
    reg = MetricsRegistry()
    reg.counter("rejects_total", reason="capacity").inc(3)
    reg.counter("rejects_total", reason="power").inc(1)
    assert reg.value("rejects_total", reason="capacity") == 3
    assert reg.value("rejects_total", reason="power") == 1
    assert reg.value("rejects_total", reason="tokens") == 0.0  # absent
    # same series object on re-request
    assert reg.counter("rejects_total", reason="capacity").value == 3
    with pytest.raises(TypeError):
        reg.gauge("rejects_total", reason="capacity")


def test_exporters_round_trip():
    reg = MetricsRegistry()
    reg.counter("a_total", help="a help").inc(2)
    reg.gauge("b", shard="0").set(1.5)
    reg.histogram("h_seconds").observe(0.25)
    snap = json.loads(reg.to_json())
    assert snap["a_total"][0] == {"labels": {}, "kind": "counter",
                                  "value": 2.0}
    assert snap["b"][0]["labels"] == {"shard": "0"}
    assert snap["h_seconds"][0]["count"] == 1
    text = reg.to_prometheus()
    assert "# HELP a_total a help" in text
    assert "# TYPE a_total counter" in text
    assert 'b{shard="0"} 1.5' in text
    assert "h_seconds_count 1" in text
    assert "_bucket" in text


def test_level_names_match_emergency_level_order():
    """The registry's canonical level labels index exactly like the
    emergency plane's per-level arrays — the naming-drift fix."""
    assert LEVEL_NAMES[CRIT_NUF] == "nuf"
    assert LEVEL_NAMES[CRIT_UF] == "uf"
    assert len(LEVEL_NAMES) == emergency.N_LEVELS


# -- audit trail ------------------------------------------------------------
def test_audit_ring_bounds_and_explain():
    trail = AuditTrail(capacity=8)
    for b in range(5):      # 5 batches x 4 rows = 20 >> capacity 8
        trail.record_batch(
            t=float(b), batch=b,
            servers=np.array([3, -1, -2, -3]),
            chassis=np.array([1, -1, -1, -1]), rule=2,
            cores=np.array([2.0, 4.0, 8.0, 1.0]),
            is_uf=np.array([True, False, True, False]),
            p95_eff=np.array([0.5, 0.25, 0.75, 1.0]),
            valid=np.ones(4, bool),
            conservative=np.zeros(4, bool), pool_left=7.0)
    assert trail.total_recorded == 20
    assert len(trail) == 8
    rows = trail.tail(8)
    assert list(rows["seq"]) == list(range(12, 20))
    rec = trail.explain(19)
    assert rec.outcome_name == "fail_pool_tokens"
    assert "REJECTED" in rec.describe()
    adm = trail.explain(16)
    assert adm.server == 3 and adm.chassis == 1 and adm.is_uf
    assert "server 3" in adm.describe()
    with pytest.raises(KeyError):
        trail.explain(0)        # fell out of the ring
    with pytest.raises(KeyError):
        trail.explain(20)       # never recorded
    rej = trail.rejected(4)
    assert all(r.outcome < 0 for r in rej)
    assert len(rej) == 4


def test_audit_skips_padding_rows():
    trail = AuditTrail(capacity=16)
    n = trail.record_batch(
        t=0.0, batch=0, servers=np.array([5, 7, -1]),
        chassis=np.array([0, 1, -1]), rule=0,
        cores=np.array([1.0, 2.0, 4.0]), is_uf=False,
        p95_eff=0.5, valid=np.array([True, False, True]),
        conservative=False, pool_left=float("inf"))
    assert n == 2
    rows = trail.tail(2)
    assert list(rows["slot"]) == [0, 2]
    assert list(rows["server"]) == [5, -1]


# -- tracer -----------------------------------------------------------------
def test_tracer_records_spans_and_totals():
    reg = MetricsRegistry()
    tr = SpanTracer(reg, capacity=4)
    for _ in range(6):
        with tr.span("place"):
            pass
    with tr.span("infer"):
        pass
    assert len(tr) == 4                     # ring bound
    totals = tr.totals()
    assert totals["place"][0] == 6          # histogram outlives ring
    assert totals["infer"][0] == 1
    names = set(tr.tail(4)["name"])
    assert "place" in names
    h = reg.histogram("serve_span_seconds", span="place")
    assert h.count == 6


class _Clock:
    """A host clock the test sets by hand."""

    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def test_span_tree_parent_batch_and_outermost_total():
    clk = _Clock()
    tr = SpanTracer(MetricsRegistry(), clock=clk)
    with tr.span("a"):                      # id 0, outermost
        clk.t = 1.0
        with tr.span("b"):                  # id 1, under a
            clk.t = 3.0
        tr.batch = 7
        with tr.span("c"):                  # id 2, under a, batch 7
            clk.t = 4.0
        tr.batch = -1
        clk.t = 5.0
    tr.record_wait("queue", 2.0)            # id 3: a wait, 3 s
    with tr.span("d"):                      # id 4, outermost
        clk.t = 6.0
    rows = {str(r["name"]): r for r in tr.tail(8)}
    assert [int(rows[n]["parent"]) for n in "abcd"] == [-1, 0, 0, -1]
    assert [int(rows[n]["batch"]) for n in "abcd"] == [-1, -1, 7, -1]
    assert rows["queue"]["wait"] and not rows["a"]["wait"]
    assert rows["queue"]["dur"] == 3.0 and rows["a"]["dur"] == 5.0
    totals = tr.totals()
    # a (5 s) and d (1 s): nested b, c and the wait are left out
    assert totals[OUTERMOST] == (2, 6.0)
    assert totals["queue"] == (1, 3.0)
    assert totals["b"] == (1, 2.0)
    # the spans a push closed outside a batch go to the batch it served
    since = tr.mark()
    with tr.span("ingest"):
        pass
    tr.batch = 8
    with tr.span("commit"):
        pass
    tr.batch = -1
    tr.claim(since, 8)
    rows = tr.tail(2)
    assert list(rows["name"]) == ["ingest", "commit"]
    assert list(rows["batch"]) == [8, 8]
    assert rows["seq"].tolist() == [5, 6]


def test_spans_land_in_the_profiler_trace(tmp_path, obs_world):
    """Under a running profiler every stage's span is a ``serve.*``
    host event of the trace, nested inside the caller's annotation."""
    import jax
    svc, table, arrivals = obs_world
    obs = Observability.full()
    pipe = _planes_pipe(svc, table, obs)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("caller"):
            _drive_planes(pipe, arrivals)
    path = next(tmp_path.rglob("*.xplane.pb"))
    events = [(e.name, e.start_ns, e.end_ns)
              for plane in jax.profiler.ProfileData.from_file(
                  str(path)).planes if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name == "caller" or e.name.startswith("serve.")]
    outer = [e for e in events if e[0] == "caller"]
    assert len(outer) == 1
    serve = [e for e in events if e[0] != "caller"]
    assert {e[0] for e in serve} == {
        "serve." + n for n in ("ingest", "merge", "featurize", "infer",
                               "place", "commit", "fetch", "record",
                               "depart", "remove", "cap", "emergency")}
    assert all(outer[0][1] <= s and e <= outer[0][2]
               for _, s, e in serve)


# -- pipeline integration ---------------------------------------------------
@pytest.fixture(scope="module")
def obs_world():
    pop = generate_population(300, seed=1)
    hist, arrivals = F.split_history_arrivals(pop)
    labels = hist.labels.astype(np.float64)
    aggs = F.subscription_aggregates(hist, labels)
    svc = train_service(F.build_features(hist, aggs),
                        labels.astype(np.int64),
                        F.p95_bucket([v.p95_util for v in hist.vms]),
                        n_trees=12)
    cap = max(v.subscription for v in hist.vms) + 8
    table = table_from_history(hist, labels, cap)
    return svc, table, arrival_batch(arrivals)


def _loaded_state(seed=3, n_servers=48, per_chassis=12, cores=40,
                  n=260):
    rng = np.random.default_rng(seed)
    st = ClusterState(n_servers=n_servers, cores_per_server=cores,
                      chassis_of_server=np.arange(n_servers)
                      // per_chassis,
                      n_chassis=n_servers // per_chassis)
    for _ in range(n):
        srv = int(rng.integers(0, n_servers))
        c = int(rng.integers(1, 8))
        if st.free_cores[srv] >= c:
            st.place(srv, c, float(rng.uniform(0.2, 1)),
                     bool(rng.random() < 0.5))
    return st


def _first_n(batch, n):
    return type(batch)(*(getattr(batch, f)[:n]
                         for f in type(batch).__dataclass_fields__))


def _pipe(svc, table, obs=None, sharded=False, budget=None):
    planes = PlaneBundle(
        emergency=EmergencyConfig.from_model(BUDGET_TIGHT), obs=obs,
        cluster_budget=None if budget is None
        else ResourceVector(watts=budget))
    kw = dict(cores_per_server=40, blades_per_chassis=12)
    if sharded:
        return ShardedServePipeline(
            svc, table, device_state(_loaded_state()),
            config=ShardedServeConfig(batch_size=32, n_shards=4,
                                      planes=planes), **kw)
    return ServePipeline(svc, table, device_state(_loaded_state()),
                         config=ServeConfig(batch_size=32,
                                            planes=planes), **kw)


def _planes_pipe(svc, table, obs=None):
    """Two ingest hosts, every plane: emergency, ballooning (its cap
    windows flush eagerly) and the adaptive controller."""
    planes = PlaneBundle(
        emergency=EmergencyConfig.from_model(BUDGET_TIGHT),
        ballooning=BallooningConfig(), adaptive=AdaptiveConfig(), obs=obs)
    return ServePipeline(svc, table, device_state(_loaded_state()),
                         cores_per_server=40, blades_per_chassis=12,
                         config=ServeConfig(batch_size=32, n_ingest_hosts=2,
                                            planes=planes))


#: power sweeps of `_drive_planes`, one unique-chassis window each
PLANE_SWEEPS = 3


def _drive_planes(pipe, arrivals):
    """A 2-host stream: arrivals dealt to both hosts, three power
    sweeps (one sample per chassis each) and one departure chunk, then
    a flush. Returns every `ServeResult`, in order."""
    out, n = [], 0
    for k in range(PLANE_SWEEPS):
        t0 = 100.0 * k
        for h in (0, 1):
            out += pipe.submit_to(
                h, _first_n(_skip(arrivals, n), 24),
                t=t0 + h + 2.0 * np.arange(24, dtype=np.float64))
            n += 24
        out += pipe.cap_to(k % 2, [0, 1, 2, 3], [2200.0, 1500.0, 2100.0,
                                                 1700.0],
                           t=t0 + 60.0 + np.arange(4.0))
        if k == 1:
            # the first batch: the hosts' first 16 arrivals each, in
            # stamp order
            first = out[0]
            idx = np.arange(48).reshape(2, 24)[:, :16].T.ravel()
            adm = np.flatnonzero(first.server >= 0)[:6]
            out += pipe.depart_to(
                0, first.server[adm], np.asarray(arrivals.cores)[idx][adm],
                first.p95_eff[adm], first.workload_type[adm] == 1,
                t=t0 + 70.0 + np.arange(len(adm), dtype=np.float64))
    tail = pipe.flush()
    return out + ([] if tail is None else [tail])


def _skip(batch, n):
    return type(batch)(*(getattr(batch, f)[n:]
                         for f in type(batch).__dataclass_fields__))


def _drive(pipe, arrivals):
    """One deterministic stream: caps, two micro-batches, departures,
    flush. Returns every `ServeResult` produced, in order."""
    out = []
    out += pipe.cap_to(0, [0, 1, 2, 3], [2200.0] * 4,
                       t=np.array([1.0, 2.0, 3.0, 4.0]))
    out += pipe.submit_to(0, _first_n(arrivals, 64),
                          t=np.arange(64, dtype=np.float64) + 10.0)
    res = [r for r in out]
    if res:
        first = res[0]
        adm = np.flatnonzero(first.server >= 0)[:6]
        out += pipe.depart_to(
            0, first.server[adm],
            np.asarray(_first_n(arrivals, 32).cores)[adm],
            first.p95_eff[adm], first.workload_type[adm] == 1,
            t=np.arange(len(adm), dtype=np.float64) + 100.0)
    tail = pipe.flush()
    if tail is not None:
        out.append(tail)
    return out


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["unsharded", "sharded"])
def test_metrics_on_is_decision_bit_identical(obs_world, sharded):
    svc, table, arrivals = obs_world
    on = _pipe(svc, table, obs=Observability.full(), sharded=sharded,
               budget=90000.0 if sharded else None)
    off = _pipe(svc, table, obs=None, sharded=sharded,
                budget=90000.0 if sharded else None)
    res_on = _drive(on, arrivals)
    res_off = _drive(off, arrivals)
    assert len(res_on) == len(res_off)
    for a, b in zip(res_on, res_off):
        assert np.array_equal(np.asarray(a.server),
                              np.asarray(b.server))
        assert np.array_equal(np.asarray(a.p95_eff),
                              np.asarray(b.p95_eff))
    # the emergency plane evolved identically too
    assert on.alarms == off.alarms
    # ... under every span the stream's path opens
    assert {"ingest", "merge", "featurize", "infer", "place", "commit",
            "fetch", "record", "depart", "queue"} \
        <= set(on.obs.tracer.totals())


def test_every_plane_on_is_decision_bit_identical(obs_world):
    """Tracing and the rest of the bundle on against off, two hosts
    and every plane: the same decisions and the same plane state."""
    import jax
    svc, table, arrivals = obs_world
    on = _planes_pipe(svc, table, Observability.full())
    off = _planes_pipe(svc, table)
    res_on, res_off = _drive_planes(on, arrivals), \
        _drive_planes(off, arrivals)
    assert len(res_on) == len(res_off) == 5
    for a, b in zip(res_on, res_off):
        assert np.array_equal(a.server, b.server)
        assert np.array_equal(a.p95_eff, b.p95_eff)
    for a, b in zip(jax.tree.leaves(jax.device_get(
            (on.emergency, on.balloon_state, on.adaptive_state,
             on.state))),
            jax.tree.leaves(jax.device_get(
                (off.emergency, off.balloon_state, off.adaptive_state,
                 off.state)))):
        assert np.array_equal(a, b)
    assert on.alarms == off.alarms
    assert {"depart", "cap", "emergency", "record", "fetch", "queue"} \
        <= set(on.obs.tracer.totals())


def test_fetch_count_follows_batches_and_sweeps(obs_world):
    """One ``fetch`` per batch (the commit) and, per power sweep, one
    for the adaptive controller's outputs and one for the balloon-cap
    window's: every host read of the stream is one of those."""
    svc, table, arrivals = obs_world
    obs = Observability.full()
    pipe = _planes_pipe(svc, table, obs)
    results = _drive_planes(pipe, arrivals)
    totals, v = obs.tracer.totals(), obs.registry.value
    batches = -(-3 * 48 // 32)
    assert len(results) == v("serve_batches_total") == batches
    assert v("serve_dispatch_total", kind="adaptive_step") \
        == v("serve_dispatch_total", kind="balloon_cap_step") \
        == PLANE_SWEEPS
    assert totals["fetch"][0] == batches + 2 * PLANE_SWEEPS
    assert totals["commit"][0] == totals["record"][0] == batches
    assert totals["cap"][0] == PLANE_SWEEPS
    assert totals["emergency"][0] == PLANE_SWEEPS
    # the departure run's push, then the gathered removal in front of
    # the next batch's placement: one ``remove`` dispatch nested in it
    assert totals["depart"][0] == 2
    assert totals["remove"][0] == 1
    # the work spans' total counts each outermost span once
    outer = ("ingest", "merge", "featurize", "infer", "place", "commit",
             "record", "depart", "cap")
    assert totals[OUTERMOST][0] == sum(totals[n][0] for n in outer)
    assert totals[OUTERMOST][1] == pytest.approx(
        sum(totals[n][1] for n in outer))


def test_queue_wait_spans_the_watermark_hold(obs_world):
    """A batch's ``queue`` span runs from the push of its oldest
    arrival to its release: here host 0's batch waits, held by the
    watermark, until host 1 first pushes."""
    svc, table, arrivals = obs_world
    clk = _Clock(10.0)
    reg = MetricsRegistry()
    obs = Observability(registry=reg, tracer=SpanTracer(reg, clock=clk))
    pipe = ServePipeline(svc, table, device_state(_loaded_state()),
                         cores_per_server=40, blades_per_chassis=12,
                         config=ServeConfig(batch_size=32, n_ingest_hosts=2,
                                            planes=PlaneBundle(obs=obs)))
    stamps = np.arange(32, dtype=np.float64) + 1.0
    assert pipe.submit_to(0, _first_n(arrivals, 16), t=stamps[:16]) == []
    clk.t = 12.0
    assert pipe.submit_to(0, _first_n(_skip(arrivals, 16), 16),
                          t=stamps[16:]) == []
    clk.t = 15.0
    out = pipe.submit_to(1, _first_n(_skip(arrivals, 32), 8),
                         t=np.arange(8, dtype=np.float64) + 40.0)
    assert len(out) == 1 and len(out[0].server) == 32
    rows = obs.tracer.tail(64)
    queue = rows[rows["name"] == "queue"]
    assert len(queue) == 1
    assert queue["dur"][0] == 5.0           # pushed at 10, released at 15
    assert queue["wait"][0] and queue["batch"][0] == 1
    assert queue["parent"][0] == -1
    # the push that released the batch is attributed to it; the two
    # pushes that served none are not
    ingest = rows[rows["name"] == "ingest"]
    assert ingest["batch"].tolist() == [-1, -1, 1]
    # no clock moved inside a call: work spans read 0, the wait 5
    assert obs.tracer.totals()[OUTERMOST][1] == 0.0


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["unsharded", "sharded"])
def test_counters_reconcile_with_decisions(obs_world, sharded):
    svc, table, arrivals = obs_world
    obs = Observability.full()
    pipe = _pipe(svc, table, obs=obs, sharded=sharded,
                 budget=90000.0 if sharded else None)
    results = _drive(pipe, arrivals)
    v = obs.registry.value
    n_arrivals = sum(len(r.server) for r in results)
    admits = sum(r.n_admitted for r in results)
    rejects = {"capacity": sum(r.n_capacity_rejected for r in results),
               "power": sum(r.n_power_rejected for r in results),
               "tokens": sum(r.n_token_rejected for r in results)}
    # exact integer reconciliation against the returned decisions
    assert v("serve_arrivals_total") == n_arrivals == 64
    assert v("serve_admits_total") == admits
    for reason, count in rejects.items():
        assert v("serve_rejects_total", reason=reason) == count
    assert (v("serve_admits_total")
            + sum(v("serve_rejects_total", reason=r)
                  for r in rejects)) == n_arrivals
    assert v("serve_batches_total") == len(results)
    assert v("serve_conservative_total") == sum(
        r.n_conservative for r in results)
    assert v("emergency_alarms_total") == pipe.alarms
    assert v("emergency_cap_windows_total") == 1
    assert v("emergency_samples_total") == 4
    # audit trail: one row per arrival, outcome codes == decisions
    assert obs.audit.total_recorded == n_arrivals
    rows = obs.audit.tail(n_arrivals)
    got = np.concatenate([np.minimum(np.asarray(r.server), 0)
                          for r in results])
    assert np.array_equal(rows["outcome"], got.astype(np.int8))
    # every admitted row names the server's real chassis
    adm = rows[rows["outcome"] == 0]
    assert (adm["chassis"] == adm["server"] // 12).all()
    # spans covered every stage
    spans = set(obs.tracer.totals())
    assert {"ingest", "merge", "featurize", "infer", "place",
            "commit"} <= spans


def test_sweep_counters_match_standalone_kernel(obs_world):
    """The fused in-scan sweep counters must agree with the standalone
    cap path's host-side sums over the same windows on an identical
    pipeline — integers exactly, watt totals to f32 accumulation
    tolerance (the scan carry adds in the state dtype)."""
    svc, table, arrivals = obs_world
    obs_fused, obs_flush = Observability(), Observability()
    fused = _pipe(svc, table, obs=obs_fused)
    flush = _pipe(svc, table, obs=obs_flush)
    caps = dict(chassis=[0, 1, 2, 3], power_w=[2200.0] * 4,
                t=np.array([1.0, 2.0, 3.0, 4.0]))
    fused.cap_to(0, caps["chassis"], caps["power_w"], t=caps["t"])
    fused.submit_to(0, _first_n(arrivals, 32),
                    t=np.arange(32, dtype=np.float64) + 10.0)
    flush.cap_to(0, caps["chassis"], caps["power_w"], t=caps["t"])
    assert flush.alarms >= 1            # property read -> standalone
    vf, vs = obs_fused.registry.value, obs_flush.registry.value
    for name in ("emergency_cap_windows_total",
                 "emergency_samples_total", "emergency_alarms_total"):
        assert vf(name) == vs(name), name
    for name in ("emergency_cut_watts_total",
                 "emergency_leftover_watts_total"):
        assert vf(name) == pytest.approx(vs(name), rel=1e-5), name
    for level in LEVEL_NAMES:
        assert vf("emergency_level_cut_watts_total", level=level) == \
            pytest.approx(vs("emergency_level_cut_watts_total",
                             level=level), rel=1e-5)
    # the achieved per-level reduction covers at least the demanded
    # cut minus what no floor could absorb (hold windows may add
    # achieved reduction with zero new demand, and p-state
    # quantization can overshoot — so >=, not ==)
    achieved = sum(vs("emergency_level_cut_watts_total", level=lv)
                   for lv in LEVEL_NAMES)
    demanded = vs("emergency_cut_watts_total")
    leftover = vs("emergency_leftover_watts_total")
    assert achieved >= demanded - leftover - 1e-3


def test_tokens_drawn_minus_credited_is_pool_delta(obs_world):
    svc, table, arrivals = obs_world
    obs = Observability()
    pipe = _pipe(svc, table, obs=obs, sharded=True, budget=90000.0)
    pool_start = pipe._pool_tokens_left()
    res = pipe.submit_to(0, _first_n(arrivals, 32),
                         t=np.arange(32, dtype=np.float64) + 10.0)
    adm = np.flatnonzero(res[0].server >= 0)[:8]
    pipe.depart_to(0, res[0].server[adm],
                   np.asarray(_first_n(arrivals, 32).cores)[adm],
                   res[0].p95_eff[adm], res[0].workload_type[adm] == 1,
                   t=np.arange(len(adm), dtype=np.float64) + 50.0)
    pipe.submit_to(0, _first_n(arrivals, 32),
                   t=np.arange(32, dtype=np.float64) + 100.0)
    pool_end = pipe._pool_tokens_left()
    v = obs.registry.value
    drawn = v("serve_tokens_drawn_total")
    credited = v("serve_tokens_credited_total")
    assert drawn > 0 and credited > 0
    # net draw == pool delta (f32 pool arithmetic on device)
    assert drawn - credited == pytest.approx(pool_start - pool_end,
                                             rel=1e-4, abs=1e-2)
    # per-shard pool gauges mirror the live pool
    gauges = sum(v("serve_pool_tokens", shard=str(i)) for i in range(4))
    assert gauges == pytest.approx(pool_end, rel=1e-6)


def test_audit_pool_left_tracks_budget(obs_world):
    svc, table, arrivals = obs_world
    obs = Observability.full()
    pipe = _pipe(svc, table, obs=obs, sharded=True, budget=90000.0)
    pipe.submit_to(0, _first_n(arrivals, 32),
                   t=np.arange(32, dtype=np.float64) + 10.0)
    rows = obs.audit.tail(32)
    assert np.isfinite(rows["pool_left"]).all()
    assert rows["pool_left"][0] == pytest.approx(
        pipe._pool_tokens_left(), rel=1e-6)


# -- sim export -------------------------------------------------------------
def test_sim_metrics_throttled_array_and_properties():
    from repro.sim.scheduler_sim import SimMetrics
    m = SimMetrics(failure_rate=0.0, empty_server_ratio=0.5,
                   chassis_score_std=0.1, server_score_std=0.2,
                   placements=10, failures=0,
                   throttled_s=np.array([30.0, 5.0]))
    assert m.nuf_throttled_s == 30.0 == m.throttled_s[CRIT_NUF]
    assert m.uf_throttled_s == 5.0 == m.throttled_s[CRIT_UF]
    # default is the all-zero per-level array
    z = SimMetrics(failure_rate=0.0, empty_server_ratio=0.0,
                   chassis_score_std=0.0, server_score_std=0.0,
                   placements=0, failures=0)
    assert z.uf_throttled_s == z.nuf_throttled_s == 0.0


def test_record_sim_metrics_schema():
    from repro.sim.scheduler_sim import SimMetrics
    reg = MetricsRegistry()
    m = SimMetrics(failure_rate=0.25, empty_server_ratio=0.5,
                   chassis_score_std=0.1, server_score_std=0.2,
                   placements=8, failures=2,
                   throttled_s=np.array([30.0, 5.0]), alarms=3,
                   migrations=1)
    record_sim_metrics(reg, m)
    assert reg.value("sim_placements_total") == 8
    assert reg.value("sim_failures_total") == 2
    assert reg.value("sim_failure_rate") == 0.25
    assert reg.value("emergency_throttled_seconds_total",
                     level="nuf") == 30.0
    assert reg.value("emergency_throttled_seconds_total",
                     level="uf") == 5.0
    assert reg.value("emergency_alarms_total") == 3
    assert reg.value("emergency_migrations_total") == 1


def test_simulate_with_obs_is_identical_and_exported():
    from repro.serve.emergency import EmergencyConfig as ECfg
    from repro.sim.scheduler_sim import (PredictionChannel,
                                         ServeBackendSpec, SimSpec,
                                         simulate)
    pol, ch = SchedulerPolicy(), PredictionChannel()
    spec = SimSpec(days=0.2, seed=4, prefill_core_ratio=0.5,
                   serve=ServeBackendSpec(
                       backend="serve-sharded", shards=2,
                       cluster_budget=ResourceVector(watts=2.0e6)),
                   emergency=ECfg.from_model(BUDGET_TIGHT))
    obs = Observability.full()
    t_on, t_off = [], []
    m_on = simulate(pol, ch, spec, trace=t_on, obs=obs)
    m_off = simulate(pol, ch, spec, trace=t_off)
    assert t_on == t_off                    # bit-identical decisions
    assert np.array_equal(m_on.throttled_s, m_off.throttled_s)
    v = obs.registry.value
    # the exporter reproduced the returned metrics exactly
    assert v("sim_placements_total") == m_on.placements
    assert v("sim_failures_total") == m_on.failures
    assert v("emergency_alarms_total") == m_on.alarms
    assert v("emergency_migrations_total") == m_on.migrations
    for i, level in enumerate(LEVEL_NAMES):
        assert v("emergency_throttled_seconds_total",
                 level=level) == m_on.throttled_s[i]
    assert v("serve_dispatch_total", kind="sharded_round") > 0
    assert {"place", "emergency"} <= set(obs.tracer.totals())


# -- monitor ----------------------------------------------------------------
def test_monitor_report_and_snapshot(tmp_path, obs_world):
    from repro.launch import monitor
    svc, table, arrivals = obs_world
    obs = Observability.full()
    pipe = _pipe(svc, table, obs=obs)
    _drive(pipe, arrivals)
    report = monitor.render_report(obs)
    assert "== metrics ==" in report
    assert "== spans ==" in report
    # the last batch's spans in order of entry, nested under parents
    tree = report.split("== spans of batch 2 ==\n")[1].splitlines()
    assert [ln.split()[0] for ln in tree[:7]] == [
        "queue", "featurize", "infer", "place", "commit", "fetch",
        "record"]
    assert tree[0].endswith("(wait)") and tree[5].startswith("    fetch")
    assert "serve_arrivals_total" in report
    assert "== audit" in report
    path = tmp_path / "snap.json"
    monitor.write_snapshot(obs, str(path))
    snap = json.loads(path.read_text())
    assert set(snap) == {"metrics", "spans", "audit", "slo",
                         "quality", "windows", "incidents"}
    assert snap["metrics"]["serve_arrivals_total"][0]["value"] == 64
    assert snap["audit"]["total_recorded"] == 64
    assert all(isinstance(r["server"], int)
               for r in snap["audit"]["tail"])
