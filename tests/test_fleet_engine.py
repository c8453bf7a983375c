"""Fleet engine acceptance: the scan/vmap-compiled jnp engine matches
the numpy oracle on the seed's Fig 4-6 configurations (per-step chassis
power, min NUF frequency, RAPL-engaged fraction), sweeps vmap cleanly,
and padding rules hold."""
import dataclasses

import numpy as np
import pytest

from repro.core.power_model import F_MAX, F_MIN
from repro.sim.chassis_sim import (paper_chassis_specs,
                                   paper_single_server_spec)
from repro.sim.fleet import (ServerSpec, VMSpec, build_layout,
                             build_uf_traces, fmin_to_pstate, frontier,
                             run_fleet, run_fleet_layouts, stack_layouts,
                             sweep_scenarios)

DUR = 60.0          # 300 steps: long enough to cover cap/lift episodes

POWER_TOL_W = 0.5           # per-step chassis power agreement
RAPL_FRAC_TOL = 0.01
FIG45 = [(230.0, "per_vm"), (230.0, "rapl"), (210.0, "per_vm")]


def _parity(specs, budget, mode, seed):
    a = run_fleet(specs, budget, mode, DUR, seed, backend="numpy")
    b = run_fleet(specs, budget, mode, DUR, seed, backend="jax")
    np.testing.assert_allclose(a.power_w, b.power_w, atol=POWER_TOL_W)
    np.testing.assert_allclose(a.min_nuf_freq, b.min_nuf_freq,
                               atol=1e-5)
    assert np.abs(a.rapl_engaged_frac
                  - b.rapl_engaged_frac).max() <= RAPL_FRAC_TOL
    np.testing.assert_allclose(a.uf_p95_latency, b.uf_p95_latency,
                               rtol=1e-3)
    np.testing.assert_allclose(a.nuf_slowdown, b.nuf_slowdown,
                               rtol=1e-3)


@pytest.mark.parametrize("budget,mode", FIG45)
def test_fig4_5_single_server_parity(budget, mode):
    _parity([paper_single_server_spec()], budget, mode, seed=3)


@pytest.mark.parametrize("balanced", [True, False])
def test_fig6_chassis_parity(balanced):
    _parity(paper_chassis_specs(balanced), 2450.0, "per_vm", seed=4)


def test_budget_batch_matches_individual_runs():
    """A vmapped cap grid produces exactly the per-budget runs."""
    specs = [paper_single_server_spec()]
    batch = run_fleet(specs, [250.0, 230.0, 210.0], "per_vm", DUR,
                      seed=3, backend="jax")
    for i, cap in enumerate((250.0, 230.0, 210.0)):
        single = run_fleet(specs, cap, "per_vm", DUR, seed=3,
                           backend="jax")
        np.testing.assert_allclose(batch.power_w[i], single.power_w[0],
                                   atol=1e-3)
        assert batch.rapl_engaged_frac[i] == pytest.approx(
            single.rapl_engaged_frac[0], abs=1e-9)


def test_heterogeneous_layouts_parity():
    """Chassis with different VM placements batch via stacked layout
    arrays; jnp matches the oracle per chassis."""
    chassis = [
        [ServerSpec(vms=[VMSpec(8, True, load=0.7),
                         VMSpec(24, False)]) for _ in range(3)],
        [ServerSpec(vms=[VMSpec(4, True, load=0.9)] * 2
                    + [VMSpec(10, False)]) for _ in range(3)],
    ]
    layouts = [build_layout(sp, pad_uf_to=6, pad_nuf_to=3)
               for sp in chassis]
    la = stack_layouts(layouts)
    n_steps = int(DUR / 0.2)
    traces = np.stack([build_uf_traces(lo, n_steps, seed=9 + i)
                       for i, lo in enumerate(layouts)])
    kw = dict(budgets_w=np.full(2, 620.0), mode="per_vm", traces=traces)
    uf_v = np.stack([lo.uf_valid for lo in layouts])
    nuf_v = np.stack([lo.nuf_valid for lo in layouts])
    nuf_c = np.stack([lo.nuf_cores for lo in layouts])
    a = run_fleet_layouts(la, uf_v, nuf_v, nuf_c, backend="numpy", **kw)
    b = run_fleet_layouts(la, uf_v, nuf_v, nuf_c, backend="jax", **kw)
    np.testing.assert_allclose(a.power_w, b.power_w, atol=POWER_TOL_W)
    np.testing.assert_allclose(a.uf_p95_latency, b.uf_p95_latency,
                               rtol=1e-3)
    assert np.abs(a.rapl_engaged_frac
                  - b.rapl_engaged_frac).max() <= RAPL_FRAC_TOL


def test_fleet_step_direct_batched_scalars():
    """fleet_step honors the documented contract without vmap: batch
    dims (B,) on the run scalars against (B, S, C) state."""
    from repro.core.fleet_dynamics import (ControlParams, RunParams,
                                           fleet_step, init_state)
    B, S, C = 3, 2, 8
    cp = ControlParams(mode="per_vm")
    uf = np.zeros((S, C), bool)
    uf[:, :4] = True
    budgets = np.array([200.0, 120.0, 90.0], np.float32)
    rp = RunParams(budgets, budgets - 5.0, budgets * 2 * 0.97,
                   np.full(B, 10, np.int32), uf, None)
    st = init_state((B,), S, C)
    util = np.ones((B, S, C), np.float32)
    for _ in range(30):
        st, outs = fleet_step(cp, rp, st, util, np)
    assert outs.server_power_w.shape == (B, S)
    # tighter per-server budgets throttle more
    assert st.freq[2].mean() < st.freq[0].mean()
    # generous chassis 0: in-band only, UF untouched; starved chassis
    # 2 trips the RAPL backstop, which throttles UF cores too
    assert (st.freq[0, :, :4] == 1.0).all()
    assert outs.rapl[2].all() and not outs.rapl[0].any()
    assert (st.freq[2, :, :4] < 1.0).all()


def test_stack_layouts_mixed_core_padding():
    """Batching a padded-core chassis with a fully-active one must keep
    the real active masks (not inherit the first layout's None)."""
    a = build_layout([ServerSpec(vms=[VMSpec(4, True), VMSpec(8, False)],
                                 n_cores=16)], pad_uf_to=1, pad_nuf_to=1,
                     pad_cores_to=24)
    b = build_layout([ServerSpec(vms=[VMSpec(4, True), VMSpec(8, False)],
                                 n_cores=24)], pad_uf_to=1, pad_nuf_to=1)
    for layouts in ([a, b], [b, a]):
        la = stack_layouts(layouts)
        assert la.active is not None
        assert la.active.shape == (2, 1, 24)
        assert {int(m.sum()) for m in la.active} == {16, 24}
    full = stack_layouts([b, b])
    assert full.active is None                   # all-active collapses


def test_core_padding_is_inert():
    """Padding the core axis must not change any metric: padded cores
    are excluded from power sums, frequency means, and app models."""
    specs = [paper_single_server_spec()]
    plain = build_layout(specs)
    padded = build_layout(specs, pad_cores_to=48)
    assert padded.active.sum() == plain.active.sum() == 40
    a = run_fleet(specs, 230.0, "per_vm", DUR, 3, backend="numpy",
                  layout=plain)
    b = run_fleet(specs, 230.0, "per_vm", DUR, 3, backend="numpy",
                  layout=padded)
    np.testing.assert_allclose(a.power_w, b.power_w, atol=1e-3)
    np.testing.assert_allclose(a.min_nuf_freq, b.min_nuf_freq, atol=0)
    assert a.uf_p95_latency[0] == pytest.approx(b.uf_p95_latency[0],
                                                rel=1e-6)


def test_sweep_scenarios_grid_and_frontier():
    """One compiled call over (budget x load x NUF-floor); uncapped
    baseline rides along; the frontier is sane."""
    specs = [paper_single_server_spec()]
    sw = sweep_scenarios(specs, [250.0, 230.0, 210.0],
                         load_scales=(1.0, 0.8), fmin_nuf=(0.5, 0.75),
                         duration_s=DUR, seed=3)
    assert sw["uf_p95_latency"].shape == (4, 2, 2)   # +1 uncapped row
    assert np.isinf(sw["budgets_w"][0])
    # uncapped row has unit latency ratio and never engages RAPL
    np.testing.assert_allclose(sw["uf_latency_ratio"][0], 1.0)
    assert sw["rapl_engaged_frac"][0].max() == 0.0
    # a shallower NUF floor (0.75) can shed less power, so RAPL engages
    # at least as often as with the deep floor at the tightest cap
    assert sw["rapl_engaged_frac"][3, 0, 1] >= \
        sw["rapl_engaged_frac"][3, 0, 0] - 1e-9
    fr = frontier(sw, provisioned_w=310.0, max_uf_latency_ratio=1.10,
                  max_rapl_frac=0.05)
    assert fr["budget_w"].shape == (2, 2)
    # lighter load can only improve (or keep) the recovered fraction
    assert (fr["oversubscription"][1] >=
            fr["oversubscription"][0] - 1e-9).all()
    assert fmin_to_pstate(F_MIN) == 10 and fmin_to_pstate(F_MAX) == 0


def _per_vm_traces(layout, n_steps, seed, load_scale=1.0):
    """The oracle: one generator per chassis and one n_steps draw per
    valid UF VM, in slot order, each VM's trace built on its own."""
    rng = np.random.default_rng(seed)
    out = np.zeros((n_steps, len(layout.uf_valid)), np.float32)
    for v in range(len(layout.uf_valid)):
        if layout.uf_valid[v]:
            wave = 0.12 * np.sin(np.linspace(0, 6 * np.pi, n_steps))
            slow = 0.06 * np.sin(np.linspace(0, 1.5 * np.pi, n_steps))
            noise = rng.normal(0, 0.03, n_steps)
            out[:, v] = np.clip(layout.uf_loads[v] + wave + slow + noise,
                                0.05, 1.2)
    return out * np.float32(load_scale)


def _campus():
    return build_layout(paper_chassis_specs(balanced=True))


def _gapped():
    """Valid UF slots that are not a prefix of the slot axis."""
    lo = build_layout([ServerSpec(vms=[VMSpec(4, True, load=0.6),
                                       VMSpec(4, True, load=0.9),
                                       VMSpec(4, True, load=0.7),
                                       VMSpec(8, False)])],
                      pad_uf_to=5)
    return dataclasses.replace(
        lo, uf_valid=np.array([True, False, True, True, False]),
        uf_loads=np.array([0.6, 0.0, 0.9, 0.7, 0.0], np.float32))


_BIG = [0, 7, 2 ** 31 - 2, 2 ** 33 + 5]
TRACE_CASES = {
    "campus": (_campus, 150, _BIG, 1.0),
    "padded_uf_slots": (lambda: build_layout(
        paper_chassis_specs(balanced=True), pad_uf_to=40), 150, _BIG, 1.0),
    "gapped_uf_slots": (_gapped, 150, [3, 11, 2 ** 32 + 1], 1.0),
    "no_uf_vm": (lambda: build_layout(
        [ServerSpec(vms=[VMSpec(8, False)] * 3)] * 2), 150, [1, 2], 1.0),
    "load_scale": (_campus, 150, _BIG, 0.8),
    "load_scale_per_seed": (_campus, 150, _BIG, [0.8, 1.0, 1.3, 0.55]),
    "short_episode": (_campus, 37, _BIG, 1.0),
    "long_episode": (lambda: build_layout([paper_single_server_spec()]),
                     3000, [5, 2 ** 31 - 2], 1.0),
    "seed_array": (_campus, 150,
                   list(np.random.default_rng(2).integers(
                       0, 2 ** 31 - 1, 64)), 1.2),
}


@pytest.mark.parametrize("case", sorted(TRACE_CASES))
def test_batched_traces_match_the_per_vm_loop(case):
    """One draw per chassis gives, bit for bit, the traces the per-VM
    loop drew: for a seed array, and for each seed alone."""
    make, n_steps, seeds, scale = TRACE_CASES[case]
    layout = make()
    seeds = np.asarray(seeds, np.int64)
    scales = np.broadcast_to(np.asarray(scale, np.float32), seeds.shape)
    want = np.stack([_per_vm_traces(layout, n_steps, int(s), sc)
                     for s, sc in zip(seeds, scales)])
    got = build_uf_traces(layout, n_steps, seeds, scale)
    assert got.dtype == np.float32
    assert got.shape == (len(seeds), n_steps, len(layout.uf_valid))
    assert np.array_equal(got, want)
    one_at_a_time = np.stack([build_uf_traces(layout, n_steps, int(s), sc)
                              for s, sc in zip(seeds, scales)])
    assert np.array_equal(got, one_at_a_time)
    assert not got[:, :, ~layout.uf_valid].any()


def test_run_fleet_draws_the_per_vm_traces():
    """run_fleet's per-seed and shared-seed traces are the oracle's,
    scaled per run in float32."""
    specs = [paper_single_server_spec()]
    layout = build_layout(specs)
    n_steps, scales = 20, np.array([1.0, 0.8, 1.1], np.float32)
    for seeds in ([4, 2 ** 31 - 2, 9], [6, 6, 6]):
        traces = np.stack([_per_vm_traces(layout, n_steps, s) * sc
                           for s, sc in zip(seeds, scales)])
        kw = dict(budgets_w=[230.0] * 3, mode="per_vm",
                  duration_s=n_steps * 0.2, backend="numpy")
        a = run_fleet(specs, seed=np.asarray(seeds), load_scale=scales,
                      **kw)
        b = run_fleet(specs, traces=traces, **kw)
        np.testing.assert_array_equal(a.power_w, b.power_w)
        np.testing.assert_array_equal(a.uf_p95_latency, b.uf_p95_latency)
