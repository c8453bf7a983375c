"""The ballooning rung of the mitigation ladder (DESIGN.md §16).

  * `balloon_step` is xp-generic and branchless: the jitted jnp twin
    is bit-equal to the numpy oracle over randomized scenarios (x64);
  * the closed-form demand really is the fixed point it claims: a
    fully served demand drops the subsequent `emergency.masked_step`
    to a zero UF p-state and no RAPL engagement, while the same
    sample un-ballooned throttles the critical level;
  * state discipline: headroom caps the grab, cleared alarms deflate
    fully, unmasked chassis pass through bit-for-bit;
  * in-sim ladder effect: cap -> balloon -> migrate reports fewer
    critical throttled-seconds (and no more migrations) than
    cap -> migrate at identical watt budgets and alarm counts — with
    the sim asserting the jnp kernel against the numpy oracle on
    every scan.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.placement import SchedulerPolicy
from repro.serve import (CRIT_NUF, CRIT_UF, BallooningConfig,
                         EmergencyConfig, balloon_demand_w,
                         balloon_step, init_ballooning, masked_step,
                         init_emergency, total_ballooned_gb)
from repro.sim.scheduler_sim import (PredictionChannel, SimSpec,
                                     simulate)

BUDGET_TIGHT = 1480.0
C = 4


def _cfg(**kw):
    return EmergencyConfig.from_model(BUDGET_TIGHT, **kw)


def _scenario(seed, n=C):
    """Randomized chassis loads: mixed NUF/UF commitments, standing
    balloons, hot and cool samples, partial masks."""
    rng = np.random.default_rng(seed)
    rho_lv = rng.uniform(10.0, 80.0, (n, 2))
    power = rng.uniform(900.0, 2600.0, n)
    mem_nuf = rng.uniform(0.0, 600.0, n)
    mask = rng.random(n) < 0.75
    standing = rng.uniform(0.0, 40.0, n) * (rng.random(n) < 0.5)
    return rho_lv, power, mem_nuf, mask, standing


@pytest.mark.parametrize("seed", range(6))
def test_jnp_twin_bit_equal_to_numpy_oracle(seed):
    """Eager jnp in x64 is bit-equal to numpy — this is the exact
    assertion the serve-backend sim re-runs on every scan.  The
    *jitted* twin is additionally held to one-ulp agreement (XLA's
    CPU backend FMA-contracts the closed form, so strict bit equality
    is not a property jit can promise)."""
    cfg, bcfg = _cfg(), BallooningConfig()
    rho_lv, power, mem_nuf, mask, standing = _scenario(seed)
    st_np = init_ballooning(C, xp=np, dtype=np.float64) \
        ._replace(ballooned_gb=standing.copy())
    st2_np, out_np = balloon_step(bcfg, cfg, st_np, rho_lv, power,
                                  mem_nuf, mask, np)
    with jax.enable_x64(True):
        st_j = init_ballooning(C, xp=jnp, dtype=jnp.float64) \
            ._replace(ballooned_gb=jnp.asarray(standing))
        args = (st_j, jnp.asarray(rho_lv), jnp.asarray(power),
                jnp.asarray(mem_nuf), jnp.asarray(mask))
        fn = lambda s, r, p, m, k: balloon_step(bcfg, cfg, s, r, p,
                                                m, k, jnp)
        st2_j, out_j = fn(*args)          # eager: the sim's oracle check
        st2_jit, out_jit = jax.jit(fn)(*args)
    np.testing.assert_array_equal(np.asarray(st2_j.ballooned_gb),
                                  st2_np.ballooned_gb)
    for name in out_np._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(out_j, name)), getattr(out_np, name),
            err_msg=name)
    np.testing.assert_allclose(np.asarray(st2_jit.ballooned_gb),
                               st2_np.ballooned_gb, rtol=1e-15)
    for name in out_np._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(out_jit, name)),
            np.asarray(getattr(out_np, name), dtype=np.float64),
            rtol=1e-15, err_msg=name)


def test_served_demand_zeroes_critical_throttle():
    """With unbounded memory headroom the closed form is exact: the
    DRAM-adjusted sample leaves the critical level at full frequency
    and RAPL disengaged, where the raw sample throttles it."""
    cfg, bcfg = _cfg(), BallooningConfig()
    rho_lv = np.tile([60.0, 40.0], (C, 1))
    power = np.full(C, 2400.0)
    mask = np.ones(C, bool)
    alarm, demand = balloon_demand_w(cfg, rho_lv, power)
    assert alarm.all() and (demand > 0).all()     # the rung is needed
    # un-ballooned: the cut overflows the NUF floor onto UF
    st_e, _ = masked_step(cfg, init_emergency(C, dtype=np.float64),
                          rho_lv, power, mask, 1.0, np)
    assert (st_e.pstate[:, CRIT_UF] > 0).all() or st_e.rapl.any()
    # ballooned with ample headroom: demand fully served
    _, bout = balloon_step(bcfg, cfg, init_ballooning(C),
                           rho_lv, power, np.full(C, 1e6), mask, np)
    assert bout.inflated.all()
    st_e2, _ = masked_step(cfg, init_emergency(C, dtype=np.float64),
                           rho_lv, bout.power_adj_w, mask, 1.0, np)
    np.testing.assert_array_equal(st_e2.pstate[:, CRIT_UF], 0)
    assert not st_e2.rapl.any()
    # NUF still does its share first — ballooning is the second rung,
    # not a bypass of the first
    assert (st_e2.pstate[:, CRIT_NUF] > 0).all()


def test_headroom_caps_grab_and_clear_deflates():
    cfg, bcfg = _cfg(), BallooningConfig(reclaim_frac=0.5)
    rho_lv = np.tile([60.0, 40.0], (C, 1))
    mem_nuf = np.full(C, 10.0)            # tiny: headroom binds
    hot = np.full(C, 2400.0)
    st = init_ballooning(C)
    st, _ = balloon_step(bcfg, cfg, st, rho_lv, hot, mem_nuf,
                         np.ones(C, bool), np)
    np.testing.assert_allclose(st.ballooned_gb, 0.5 * mem_nuf)
    assert total_ballooned_gb(st) == pytest.approx(0.5 * mem_nuf.sum())
    # a cool sample deflates the standing balloon completely
    cool = np.full(C, 500.0)
    st2, out2 = balloon_step(bcfg, cfg, st, rho_lv, cool, mem_nuf,
                             np.ones(C, bool), np)
    np.testing.assert_allclose(out2.released_gb, st.ballooned_gb)
    np.testing.assert_array_equal(st2.ballooned_gb, 0.0)
    assert not out2.inflated.any()


def test_unmasked_chassis_pass_through():
    cfg, bcfg = _cfg(), BallooningConfig()
    rho_lv, power, mem_nuf, _, standing = _scenario(11)
    mask = np.array([True, False, True, False])
    st = init_ballooning(C)._replace(ballooned_gb=standing.copy())
    st2, out = balloon_step(bcfg, cfg, st, rho_lv, power, mem_nuf,
                            mask, np)
    np.testing.assert_array_equal(st2.ballooned_gb[~mask],
                                  standing[~mask])
    np.testing.assert_array_equal(out.power_adj_w[~mask], power[~mask])
    np.testing.assert_array_equal(out.absorbed_w[~mask], 0.0)


def test_sim_ladder_beats_cap_migrate():
    """cap -> balloon -> migrate vs cap -> migrate on the same trace:
    identical alarms, strictly fewer critical throttled-seconds, no
    more migrations — and the serve scan asserts the jnp ballooning
    kernel bit-equal to the numpy oracle in-sim."""
    pol, ch = SchedulerPolicy(alpha=0.8), PredictionChannel("ml")
    kw = dict(days=0.1, seed=0, deployments_per_hour=16.0,
              prefill_core_ratio=0.6)
    ecfg = _cfg(dwell_s=120.0)
    base = simulate(pol, ch, SimSpec(emergency=ecfg, **kw))
    rung = simulate(pol, ch, SimSpec(emergency=ecfg,
                                     ballooning=BallooningConfig(),
                                     **kw))
    assert base.alarms == rung.alarms > 0
    assert rung.balloon_events > 0
    assert rung.balloon_reclaimed_gb > 0
    assert rung.uf_throttled_s < base.uf_throttled_s
    assert rung.migrations <= base.migrations
    assert base.balloon_events == 0
    # decisions (placements) are untouched — ballooning acts after
    # admission, on the power plane only
    for f in ("placements", "failures", "failure_rate"):
        assert getattr(base, f) == getattr(rung, f)
