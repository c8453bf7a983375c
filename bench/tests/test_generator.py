"""The stream generator draws the paper's Table I mix, and the same
seed gives the same stream."""
import numpy as np
import pytest

from bench.reference.fleet_ref import Chassis
from bench.traffic import generator as gen


def test_tables_are_the_programs():
    from repro.sim import telemetry as T
    np.testing.assert_array_equal(gen.CORE_SIZES, T.CORE_SIZES)
    np.testing.assert_array_equal(gen.CORE_PROBS, T.CORE_PROBS)
    np.testing.assert_array_equal(gen.LIFETIME_BUCKETS, T.LIFETIME_BUCKETS)
    np.testing.assert_array_equal(gen.LIFETIME_PROBS, T.LIFETIME_PROBS)
    np.testing.assert_array_equal(gen.DEPLOY_SIZE_BUCKETS,
                                  T.DEPLOY_SIZE_BUCKETS)
    np.testing.assert_array_equal(gen.DEPLOY_SIZE_PROBS, T.DEPLOY_SIZE_PROBS)


def _bucket_freq(values, buckets):
    idx = np.searchsorted(np.asarray(buckets)[:, 1], values)
    return np.bincount(idx, minlength=len(buckets)) / len(values)


@pytest.fixture(scope="module")
def stream():
    rng = np.random.default_rng(20260)
    subs, _ = gen.history(rng, 1500)
    return gen.serve_stream(rng, subs, 200_000, 2600.0, 2)


def test_core_and_lifetime_frequencies(stream):
    v = stream.vms
    freq = np.array([(v.cores == c).mean() for c in gen.CORE_SIZES])
    np.testing.assert_allclose(freq, gen.CORE_PROBS, atol=0.005)
    np.testing.assert_allclose(_bucket_freq(v.lifetime_h,
                                            gen.LIFETIME_BUCKETS),
                               gen.LIFETIME_PROBS, atol=0.005)
    per_core = v.memory_gb / v.cores
    np.testing.assert_allclose([(per_core == m).mean() for m in (2, 4, 8)],
                               1 / 3, atol=0.005)


def test_deployment_sizes_and_subscriptions(stream):
    size = np.diff(stream.start)
    np.testing.assert_allclose(_bucket_freq(size, gen.DEPLOY_SIZE_BUCKETS),
                               gen.DEPLOY_SIZE_PROBS, atol=0.01)
    sub = stream.vms.subscription
    first = sub[stream.start[:-1]]
    assert (np.repeat(first, size) == sub[:stream.start[-1]]).all()


def test_stamps_rate_and_order(stream):
    t = stream.t
    assert (np.diff(t) > 0).all()
    rate = len(t) / t[-1]
    assert abs(rate / 2600.0 - 1) < 0.02


def test_same_seed_same_stream():
    def draw(seed):
        rng = np.random.default_rng(seed)
        subs, _ = gen.history(rng, 300)
        return gen.serve_stream(rng, subs, 5000, 1000.0, 2)
    a, b, c = draw(2 ** 33 + 5), draw(2 ** 33 + 5), draw(2 ** 33 + 6)
    for f in ("subscription", "cores", "lifetime_h", "p95_util"):
        np.testing.assert_array_equal(getattr(a.vms, f), getattr(b.vms, f))
    np.testing.assert_array_equal(a.t, b.t)
    assert not np.array_equal(a.vms.cores[:5000], c.vms.cores[:5000])


def test_fleet_traces_match_the_engine():
    from repro.sim.chassis_sim import paper_chassis_specs
    from repro.sim.fleet import build_layout, build_uf_traces
    layout = build_layout(paper_chassis_specs(balanced=True))
    servers = [[{"cores": 4, "uf": True, "load": 0.85}] * 3
               + [{"cores": 6, "uf": False, "load": 0.75}] * 3] * 12
    ch = Chassis(servers)
    np.testing.assert_array_equal(gen.uf_load_traces(987654321, 150,
                                                     ch.loads),
                                  build_uf_traces(layout, 150, 987654321))


def test_table_means():
    assert gen.MEAN_CORES == pytest.approx(4.35)
    hours = np.concatenate([np.arange(a, b + 1) for a, b in
                            gen.LIFETIME_BUCKETS])
    probs = np.concatenate([np.full(b - a + 1, p / (b - a + 1)) for
                            (a, b), p in zip(gen.LIFETIME_BUCKETS,
                                             gen.LIFETIME_PROBS)])
    assert gen.MEAN_LIFETIME_H == pytest.approx((hours * probs).sum())


def test_residual_lifetimes_are_stationary():
    # a stationary population's residual life has mean E[L^2] / 2 E[L]
    rng = np.random.default_rng(77)
    life = gen.sample_bucket(rng, gen.LIFETIME_BUCKETS, gen.LIFETIME_PROBS,
                             2_000_000).astype(np.float64)
    want = (life ** 2).mean() / (2 * life.mean())
    got = gen.residual_lifetimes_h(rng, 400_000)
    assert got.mean() == pytest.approx(want, rel=0.01)
    assert got.max() <= gen.MAX_LIFETIME_H


def test_stationary_stream_fill_then_stream():
    rng = np.random.default_rng(2 ** 33 + 9)
    subs, _ = gen.history(rng, 300)
    s = gen.stationary_stream(rng, subs, 3000, 1.0, 20_000, 1800.0, 2)
    assert (np.diff(s.t) > 0).all()
    # a residual lifetime is a fraction of an hour; a lifetime is whole
    n_fill = int(np.nonzero(s.depart_h != s.vms.lifetime_h)[0][-1]) + 1
    assert 3000 <= n_fill <= 3000 + 60
    assert s.t[n_fill - 1] < 1.5 <= s.t[n_fill] + 0.5
    assert s.start[-1] == len(s.vms) == len(s.depart_h)
    fill, main = s.depart_h[:n_fill], s.depart_h[n_fill:]
    np.testing.assert_array_equal(main, s.vms.lifetime_h[n_fill:])
    assert (fill <= s.vms.lifetime_h.max()).all()
    rate = (len(s.t) - n_fill) / (s.t[-1] - s.t[n_fill])
    assert abs(rate / 1800.0 - 1) < 0.05
