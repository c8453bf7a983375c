"""Closed-loop adaptive oversubscription (`repro.serve.adaptive`) —
oracle parity, controller semantics, and pipeline/sim wiring.

The contract under test (docs/adaptive.md, DESIGN.md §15):

  * the branchless numpy scan is the oracle and the compiled jnp twin
    is bit-identical to it, scan for scan (f32 and x64-f64);
  * the controller ratchets up slowly on stable quorum, backs off
    fast on any hot chassis or a broken quorum, clamps to
    ``[ratio_min, ratio_max]``, and holds 1.0 with no history;
  * `retarget_pool` mints/retires only the free allowance — tokens
    committed to placed VMs are never revoked;
  * a `ServePipeline` with `PlaneBundle(adaptive=...)` scans eagerly
    per cap window, and the 1-shard `ShardedServePipeline` reproduces
    it ratio for ratio (both equal to a hand-stepped numpy oracle);
  * `SimSpec(adaptive=...)` requires a serve backend, and
    'serve' == 'serve-sharded' @ 1 shard trace-for-trace with the
    controller live.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.placement import SchedulerPolicy
from repro.obs import AdaptiveRecord, Observability
from repro.serve import (REASON_NAMES, AdaptiveConfig, PlaneBundle,
                         ResourceVector, ServeConfig,
                         ServePipeline, ShardedServeConfig,
                         ShardedServePipeline, adaptive_step,
                         decision_reason, init_adaptive, offered_power,
                         retarget_pool)
from repro.sim.scheduler_sim import (PredictionChannel, ServeBackendSpec,
                                     SimSpec, simulate)

C = 6              # chassis in the kernel-level tests


def _cfg(**kw) -> AdaptiveConfig:
    kw.setdefault("window", 8)
    kw.setdefault("min_history", 3)
    return AdaptiveConfig(**kw)


def _scan_stream(cfg, utils, xp=np, dtype=np.float64):
    """Step a C-chassis controller through a (T, C) utilization
    stream (powers synthesized through `offered_power`, the sim's
    feed), returning the state and per-scan outputs."""
    rho_lv = xp.asarray(np.full((C, 2), 40.0, dtype))
    st = init_adaptive(cfg, C, xp=xp, dtype=dtype)
    outs = []
    for u in utils:
        pw = offered_power(cfg, rho_lv, xp.asarray(u, dtype), xp)
        st, out = adaptive_step(cfg, st, rho_lv, pw,
                                xp.ones(C, bool), xp)
        outs.append(out)
    return st, outs


# --- controller semantics -------------------------------------------------

def test_config_validation():
    with pytest.raises(ValueError):
        AdaptiveConfig(window=1)
    with pytest.raises(ValueError):
        AdaptiveConfig(window=8, min_history=9)
    with pytest.raises(ValueError):
        AdaptiveConfig(spread_q_lo=0.9, spread_q_hi=0.1)
    with pytest.raises(ValueError):
        AdaptiveConfig(backoff_quorum=0.95, ratchet_quorum=0.9)


def test_zero_history_holds_ratio_one():
    """No samples, no oversubscription: an all-masked-out scan leaves
    the ratio at 1.0 and classifies as hold_no_history."""
    cfg = _cfg()
    st = init_adaptive(cfg, C)
    rho = np.full((C, 2), 40.0)
    st, out = adaptive_step(cfg, st, rho, np.full(C, 500.0),
                            np.zeros(C, bool), np)
    assert float(out.ratio) == 1.0
    assert int(out.n_known) == 0 and not bool(out.backoff)
    r = decision_reason(1.0, float(out.ratio), int(out.n_known),
                        bool(out.ratchet), bool(out.backoff),
                        bool(out.hot))
    assert REASON_NAMES[r] == "hold_no_history"


def test_min_history_gates_the_first_decision():
    """The ratio must not move before any window reaches min_history
    samples, however stable the early stream looks."""
    cfg = _cfg(min_history=4)
    st, outs = _scan_stream(cfg, [np.full(C, 0.4)] * 3)
    assert all(float(o.ratio) == 1.0 for o in outs)
    assert int(outs[-1].n_known) == 0


def test_steady_windows_ratchet_to_ceiling():
    """A flat, cool stream ratchets by step_up per scan once known,
    then clamps at ratio_max (ratchet_ceiling)."""
    cfg = _cfg(step_up=0.25, ratio_max=1.6)
    st, outs = _scan_stream(cfg, [np.full(C, 0.4)] * 8)
    ratios = [float(o.ratio) for o in outs]
    assert ratios[1] == 1.0                       # still gathering
    assert ratios[-1] == pytest.approx(1.6)       # pinned at max
    assert int(st.ratchets) >= 3
    last = outs[-1]
    r = decision_reason(1.6, float(last.ratio), int(last.n_known),
                        bool(last.ratchet), bool(last.backoff),
                        bool(last.hot))
    assert REASON_NAMES[r] == "ratchet_ceiling"


def test_hot_sample_backs_off_fast():
    """One hot chassis collapses the ratio by step_down (several
    up-steps at once) regardless of the stable quorum."""
    cfg = _cfg(step_up=0.05, step_down=0.25, ratio_max=3.0)
    utils = [np.full(C, 0.4)] * 6
    hot = np.full(C, 0.4)
    hot[2] = 0.95
    st, outs = _scan_stream(cfg, utils + [hot])
    before, after = float(outs[-2].ratio), float(outs[-1].ratio)
    assert bool(outs[-1].hot) and bool(outs[-1].backoff)
    assert after == pytest.approx(max(before - 0.25, 1.0))
    r = decision_reason(before, after, int(outs[-1].n_known),
                        bool(outs[-1].ratchet), bool(outs[-1].backoff),
                        bool(outs[-1].hot))
    assert REASON_NAMES[r] == "backoff_hot"


def test_oscillating_windows_pin_the_floor():
    """A thrashing stream (sign flip every delta) never ratchets: the
    flip-rate assesser keeps every window unstable and the ratio
    stays at ratio_min (backoff_floor once known)."""
    cfg = _cfg(flip_thresh=0.5)
    utils = [np.full(C, 0.3 + 0.2 * (k % 2)) for k in range(10)]
    st, outs = _scan_stream(cfg, utils)
    assert all(float(o.ratio) == 1.0 for o in outs)
    last = outs[-1]
    assert int(last.n_known) == C and bool(last.backoff)
    r = decision_reason(1.0, 1.0, int(last.n_known), bool(last.ratchet),
                        bool(last.backoff), bool(last.hot))
    assert REASON_NAMES[r] == "backoff_floor"
    assert int(st.backoffs) > 0


def test_masked_chassis_keep_their_windows():
    """A scan whose mask excludes a chassis must leave that chassis'
    window (count, samples) untouched while the rest advance."""
    cfg = _cfg()
    rho = np.full((C, 2), 40.0)
    st = init_adaptive(cfg, C)
    mask = np.ones(C, bool)
    mask[0] = False
    pw = np.asarray(offered_power(cfg, rho, 0.4, np))
    st, _ = adaptive_step(cfg, st, rho, pw, mask, np)
    assert int(st.count[0]) == 0
    assert (np.asarray(st.count)[1:] == 1).all()


def test_spread_assesser_rejects_wide_band():
    """Same mean, wide percentile spread -> unstable even with a low
    flip rate (a monotone ramp has zero flips)."""
    cfg = _cfg(spread_thresh=0.1, flip_thresh=1.0)
    ramp = [np.full(C, 0.12 * k) for k in range(8)]
    _, outs = _scan_stream(cfg, ramp)
    assert int(outs[-1].n_stable) == 0
    assert all(float(o.ratio) == 1.0 for o in outs)


# --- numpy <-> jnp bit-equality -------------------------------------------

@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_jnp_twin_bit_equal_to_numpy_oracle(dtype):
    """The compiled twin reproduces the numpy oracle bit for bit over
    a randomized stream — masked writes, percentile gathers, flip
    counts, and the fleet reduction included."""
    rng = np.random.default_rng(0)
    cfg = _cfg(ratio_max=3.0)
    rho = rng.uniform(5.0, 80.0, (C, 2)).astype(dtype)
    stn = init_adaptive(cfg, C, xp=np, dtype=dtype)
    ctx = jax.enable_x64(True) if dtype == np.float64 \
        else contextlib_null()
    with ctx:
        stj = jax.tree.map(jnp.asarray, stn)
        for _ in range(12):
            u = rng.uniform(0.0, 1.1, C).astype(dtype)
            mask = rng.random(C) < 0.7
            pw = np.asarray(offered_power(cfg, rho, u, np), dtype)
            stn, outn = adaptive_step(cfg, stn, rho, pw, mask, np)
            stj, outj = adaptive_step(cfg, stj, jnp.asarray(rho),
                                      jnp.asarray(pw),
                                      jnp.asarray(mask), jnp)
            for a, b in zip(stn, stj):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))
            for a, b in zip(outn, outj):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))


def contextlib_null():
    import contextlib
    return contextlib.nullcontext()


# --- pool retargeting -----------------------------------------------------

def test_retarget_pool_mints_and_retires_only_free_tokens():
    cfg = _cfg()
    base, committed = 100.0, 40.0
    # ratchet: allowance grows -> free pool widens
    assert float(retarget_pool(cfg, base, 1.5, committed, np)) \
        == pytest.approx(110.0)
    # back-off below commitment: free pool drains to zero, committed
    # tokens stay out (never negative, never revoked)
    assert float(retarget_pool(cfg, base, 1.0, 120.0, np)) == 0.0


def test_retarget_pool_conserves_through_mint_retire_sequences():
    """Through any ratio walk, committed + free ==
    max(base * ratio, committed) — the §10 conservation invariant
    with the controller in the loop."""
    rng = np.random.default_rng(1)
    cfg = _cfg()
    base = np.array([80.0, 120.0, 60.0, 140.0])
    committed = np.zeros(4)
    for _ in range(50):
        ratio = float(rng.uniform(1.0, 3.0))
        free = np.asarray(retarget_pool(cfg, base, ratio, committed, np))
        np.testing.assert_allclose(
            committed + free, np.maximum(base * ratio, committed))
        # commit some of the free pool (placements), release some
        committed = committed + rng.uniform(0, 1, 4) * free
        committed = np.maximum(
            committed - rng.uniform(0, 10, 4), 0.0)


def test_decision_reason_covers_every_branch():
    cases = {
        "hold_no_history": (1.0, 1.0, 0, False, False, False),
        "hold_band": (1.2, 1.2, 5, False, False, False),
        "ratchet_quorum": (1.2, 1.25, 5, True, False, False),
        "ratchet_ceiling": (2.0, 2.0, 5, True, False, False),
        "backoff_hot": (1.5, 1.25, 5, False, True, True),
        "backoff_quorum": (1.5, 1.25, 5, False, True, False),
        "backoff_floor": (1.0, 1.0, 5, False, True, True),
    }
    for name, args in cases.items():
        assert REASON_NAMES[decision_reason(*args)] == name


# --- pipeline wiring ------------------------------------------------------

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


PIPE_KW = dict(n_servers=48, cores_per_server=40, blades_per_chassis=12)


def _cap_stream(pipe, n_scans=6, power=500.0):
    """Push `n_scans` full-fleet constant power sweeps (an empty
    cluster reads util 0 -> every window stabilizes -> ratchet)."""
    idx = np.arange(4)
    for k in range(n_scans):
        t0 = float(k + 1)
        pipe.cap_to(0, idx, np.full(4, power), t=t0 + (idx + 1) * 1e-7)
    pipe.flush()


def test_pipeline_ratio_ratchets_and_scales_rho_cap(serve_world):
    svc, hist, labels, _ = serve_world
    acfg = _cfg(ratio_max=2.0)
    obs = Observability.full()
    pipe = ServePipeline.from_history(
        svc, hist, labels,
        config=ServeConfig(batch_size=32,
                           planes=PlaneBundle(adaptive=acfg, obs=obs)),
        **PIPE_KW)
    base_cap = np.asarray(pipe.rho_cap).copy()
    _cap_stream(pipe)
    r = pipe.adaptive_ratio
    assert r > 1.0
    np.testing.assert_allclose(np.asarray(pipe.rho_cap), base_cap * r)
    # the decision trail and metrics recorded every scan
    assert obs.adaptive.total_recorded == 6
    snap = obs.registry.snapshot()
    assert snap["adaptive_ratio"][0]["value"] == pytest.approx(r)
    assert snap["adaptive_ratchet_total"][0]["value"] > 0
    rows = obs.adaptive.tail(6)
    assert any(AdaptiveRecord(row).reason_name.startswith("ratchet")
               for row in rows)


def test_cap_to_accepted_with_adaptive_only(serve_world):
    """cap_to must work with adaptive_cfg alone (no emergency plane) —
    and still raise with neither plane configured."""
    svc, hist, labels, _ = serve_world
    pipe = ServePipeline.from_history(
        svc, hist, labels,
        config=ServeConfig(batch_size=32,
                           planes=PlaneBundle(adaptive=_cfg())),
        **PIPE_KW)
    pipe.cap_to(0, [0], [500.0])
    pipe.flush()
    assert pipe.adaptive_state is not None
    bare = ServePipeline.from_history(
        svc, hist, labels, config=ServeConfig(batch_size=32), **PIPE_KW)
    with pytest.raises(ValueError):
        bare.cap_to(0, [0], [500.0])


def test_one_shard_sharded_matches_unsharded_and_numpy_oracle(
        serve_world):
    """1-shard sharded pipeline == unsharded pipeline == hand-stepped
    numpy oracle, ratio for ratio and window for window, on the same
    cap stream."""
    svc, hist, labels, _ = serve_world
    acfg = _cfg(ratio_max=2.0)
    base = ServePipeline.from_history(
        svc, hist, labels,
        config=ServeConfig(batch_size=32,
                           planes=PlaneBundle(adaptive=acfg)),
        **PIPE_KW)
    shp = ShardedServePipeline.from_history(
        svc, hist, labels,
        config=ShardedServeConfig(batch_size=32, n_shards=1,
                                  planes=PlaneBundle(adaptive=acfg)),
        **PIPE_KW)
    for pipe in (base, shp):
        _cap_stream(pipe)
    # numpy oracle on the same stream: empty cluster -> rho_lv = 0
    st = init_adaptive(acfg, 4, xp=np, dtype=np.float32)
    for _ in range(6):
        st, _ = adaptive_step(acfg, st, np.zeros((4, 2), np.float32),
                              np.full(4, 500.0, np.float32),
                              np.ones(4, bool), np)
    want = float(st.ratio)
    assert base.adaptive_ratio == pytest.approx(want)
    assert float(shp.adaptive_ratio[0]) == pytest.approx(want)
    a, b = base.adaptive_state, shp.adaptive_state
    for xa, xb, xn in zip(a, b, st):
        np.testing.assert_array_equal(np.asarray(xa),
                                      np.asarray(xb)[0])
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xn))


def test_sharded_backoff_drains_only_free_pool(serve_world):
    """With a finite cluster budget, a controller back-off retargets
    the free pool but never below zero and never touches committed
    rho (mint/retire is free-side only)."""
    from repro.sim.telemetry import arrival_batch, arrival_stamps
    svc, hist, labels, arrivals = serve_world
    acfg = _cfg(ratio_max=3.0)
    shp = ShardedServePipeline.from_history(
        svc, hist, labels,
        config=ShardedServeConfig(
            batch_size=32, n_shards=1,
            planes=PlaneBundle(
                adaptive=acfg,
                cluster_budget=ResourceVector(watts=40000.0))),
        **PIPE_KW)
    _cap_stream(shp)                          # ratchets: pool widens
    pool_up = float(np.asarray(shp.sharded.pool)[:, 0].sum())
    # commit real VMs so power samples read back as utilization...
    idx64 = np.arange(64)
    shp.submit_to(0, arrival_batch(arrivals, idx64),
                  t=50.0 + arrival_stamps(64))
    shp.flush()
    committed = np.asarray(shp.sharded.shards.rho_peak).sum()
    assert committed > 0
    # ...then run the fleet hot: back-off drains the free pool but
    # never below zero and never touches committed rho
    idx = np.arange(4)
    for k in range(8):
        shp.cap_to(0, idx, np.full(4, 6000.0),
                   t=200.0 + k + (idx + 1) * 1e-7)
    shp.flush()
    pool_down = float(np.asarray(shp.sharded.pool)[:, 0].sum())
    assert pool_down < pool_up
    assert pool_down >= 0.0
    np.testing.assert_array_equal(
        np.asarray(shp.sharded.shards.rho_peak).sum(), committed)


# --- sim wiring -----------------------------------------------------------

SIM_KW = dict(days=0.08, seed=3, deployments_per_hour=16.0,
              prefill_core_ratio=0.5)


def _sim_spec(acfg, backend="serve", shards=1):
    return SimSpec(serve=ServeBackendSpec(
        backend=backend, shards=shards,
        admission_budget=ResourceVector(watts=12 * 310.0 / 2)),
        adaptive=acfg, **SIM_KW)


def test_sim_adaptive_requires_serve_backend():
    with pytest.raises(ValueError, match="serve"):
        simulate(SchedulerPolicy(), PredictionChannel("ml"),
                 SimSpec(adaptive=_cfg(), **SIM_KW))


def test_sim_adaptive_ratchets_and_asserts_twin():
    """A short serve-backend run with the controller live: the ratio
    moves off 1.0, steps are counted, and every scan asserted the
    compiled twin bit-equal in-sim (the assert is inside the scan)."""
    m = simulate(SchedulerPolicy(), PredictionChannel("ml"),
                 _sim_spec(_cfg(ratio_max=3.0)))
    assert m.adaptive_ratio > 1.0
    assert m.adaptive_ratchets > 0
    assert m.placements > 0


def test_sim_one_shard_sharded_identical_with_adaptive():
    """'serve' == 'serve-sharded' @ 1 shard, trace for trace, with
    the adaptive controller scaling admission on both paths."""
    acfg = _cfg(ratio_max=3.0)
    tr_s, tr_sh = [], []
    ms = simulate(SchedulerPolicy(), PredictionChannel("ml"),
                  _sim_spec(acfg), trace=tr_s)
    msh = simulate(SchedulerPolicy(), PredictionChannel("ml"),
                   _sim_spec(acfg, backend="serve-sharded"),
                   trace=tr_sh)
    assert tr_s == tr_sh
    assert ms.adaptive_ratio == msh.adaptive_ratio
    assert ms.adaptive_ratchets == msh.adaptive_ratchets
    assert ms.failure_rate == msh.failure_rate


def test_sim_metrics_export_through_obs_registry():
    obs = Observability.full()
    m = simulate(SchedulerPolicy(), PredictionChannel("ml"),
                 _sim_spec(_cfg(ratio_max=2.0)), obs=obs)
    snap = obs.registry.snapshot()
    assert snap["adaptive_ratio"][0]["value"] \
        == pytest.approx(m.adaptive_ratio)
    assert snap["adaptive_ratchet_total"][0]["value"] \
        == m.adaptive_ratchets
