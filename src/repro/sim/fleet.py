"""Fleet-scale simulation engine: scan/vmap-compiled capping dynamics.

The seed simulated one chassis at a time in a 200 ms-step Python loop;
the paper's headline results (Figs 4-7, Table IV) need capping dynamics
and policy sweeps over a whole fleet. This module makes the substrate
dense, fixed-shape, and compiled — the same transformation applied to
forest inference in `kernels/forest`:

  * `run_fleet(..., backend='jax')` — `jax.lax.scan` over time steps,
    `jax.vmap` over chassis, one `jax.jit`-compiled call simulating a
    (n_chassis, n_steps) grid. Figs 4-6 are slices of a fleet run.
  * `run_fleet(..., backend='numpy')` — the validation oracle: the SAME
    `repro.core.fleet_dynamics.fleet_step` arithmetic, stepped in a
    plain Python loop one chassis at a time (the seed's execution
    model, kept as ground truth and as the benchmark baseline).
  * `sweep_scenarios` — vmaps the engine across grids of chassis
    budgets, offered-load scales, and NUF frequency floors
    (`OversubConfig.fmin_nuf`), producing Table IV-style frontiers in
    one compiled call. Different chassis *layouts* (the beta/UF-fraction
    axis, heterogeneous VM placements) batch the layout arrays instead
    — see `run_fleet_layouts`.

State layout and padding rules are documented in DESIGN.md §8.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple

import numpy as np

from repro.core.fleet_dynamics import (ALERT_FRACTION, ALERT_MARGIN_W,
                                       FREQ_TABLE, POLL_INTERVAL_S,
                                       ControlParams, RunParams,
                                       fleet_step, init_state)
from repro.core.power_model import (F_MAX, F_MIN, N_PSTATES,
                                    ServerPowerModel)

_F32 = np.float32


# --- workload specification (the seed's vocabulary, unchanged) -----------

@dataclass
class VMSpec:
    n_cores: int
    is_uf: bool
    #: offered load as a fraction of the VM's full-frequency capacity
    load: float = 0.75


@dataclass
class ServerSpec:
    vms: list                       # list[VMSpec]; sum cores <= n_cores
    n_cores: int = 40


# --- padded fleet layout --------------------------------------------------

class LayoutArrays(NamedTuple):
    """The per-chassis array pytree the engine consumes. Shared across a
    homogeneous fleet (vmap in_axes=None) or batched with a leading B
    axis for heterogeneous placements (in_axes=0; see stack_layouts).

    Per-step UF capacity uses a compact gather of only the UF cores
    (uf_core_idx/uf_compact) instead of a full (S*C)-wide one-hot
    matmul, and the NUF work integral is accumulated as a raw frequency
    sum and reduced by nuf_onehot ONCE after the scan — both measured
    wins for the compiled fleet step."""
    uf_mask: Any        # (S, C) bool
    nuf_core: Any       # (S, C) bool
    active: Any         # (S, C) bool or None (= all cores real)
    uf_id: Any          # (S*C,) i32, owning UF VM (Vu = unowned)
    uf_core_idx: Any    # (Ku,) i32, flat indices of UF cores (0-padded)
    uf_compact: Any     # (Ku, Vu) f32, UF-core -> VM membership
    uf_cores: Any       # (Vu,) f32
    nuf_onehot: Any     # (Vn, S*C) f32
    nuf_cores: Any      # (Vn,) f32


@dataclass(frozen=True)
class FleetLayout:
    """Dense, fixed-shape view of one chassis' VM placement.

    Core-level masks are (S, C); VM-level reductions are one-hot
    matrices over the flattened (S*C,) core axis so per-VM capacity is
    a single matmul. VM slots beyond the real count are padding
    (`*_valid` False, zero one-hot rows)."""
    n_servers: int
    n_cores: int
    uf_mask: np.ndarray        # (S, C) bool — cores of user-facing VMs
    nuf_core: np.ndarray       # (S, C) bool — cores of batch VMs
    active: np.ndarray         # (S, C) bool — core exists (not padding)
    uf_onehot: np.ndarray      # (Vu, S*C) f32 — membership of UF VM v
    uf_cores: np.ndarray       # (Vu,) f32
    uf_loads: np.ndarray       # (Vu,) f32 — base offered load
    uf_valid: np.ndarray       # (Vu,) bool
    nuf_onehot: np.ndarray     # (Vn, S*C) f32
    nuf_cores: np.ndarray      # (Vn,) f32
    nuf_valid: np.ndarray      # (Vn,) bool
    uf_id: np.ndarray          # (S*C,) i32 — owning UF VM, Vu = none
    nuf_id: np.ndarray         # (S*C,) i32 — owning NUF VM, Vn = none

    def arrays(self, pad_uf_cores_to: int = 0) -> LayoutArrays:
        """The pytree the engine closes over / vmaps. `active` is None
        when every core is real — the transition then skips all padding
        masks (see fleet_dynamics.server_power)."""
        n_uf = len(self.uf_valid)
        idx = np.nonzero(self.uf_id < n_uf)[0]
        ku = max(len(idx), pad_uf_cores_to, 1)
        core_idx = np.zeros(ku, np.int32)
        core_idx[:len(idx)] = idx
        compact = np.zeros((ku, n_uf), _F32)
        compact[np.arange(len(idx)), self.uf_id[idx]] = 1.0
        return LayoutArrays(self.uf_mask, self.nuf_core,
                            None if self.active.all() else self.active,
                            self.uf_id, core_idx, compact, self.uf_cores,
                            self.nuf_onehot, self.nuf_cores)


def build_layout(specs: list, pad_uf_to: int = 0, pad_nuf_to: int = 0,
                 pad_cores_to: int = 0) -> FleetLayout:
    """Pack a list[ServerSpec] into padded fleet arrays. VM walk order
    (server-major, then VM) matches the seed simulator, so load traces
    drawn per-VM consume the rng stream identically."""
    n_servers = len(specs)
    n_cores = max(pad_cores_to, max(s.n_cores for s in specs))
    uf_mask = np.zeros((n_servers, n_cores), bool)
    nuf_core = np.zeros((n_servers, n_cores), bool)
    active = np.zeros((n_servers, n_cores), bool)
    uf_members, uf_loads, nuf_members = [], [], []
    for si, spec in enumerate(specs):
        active[si, :spec.n_cores] = True
        c0 = 0
        for vm in spec.vms:
            cores = np.zeros((n_servers, n_cores), bool)
            cores[si, c0:c0 + vm.n_cores] = True
            if vm.is_uf:
                uf_mask |= cores
                uf_members.append(cores.ravel())
                uf_loads.append(vm.load)
            else:
                nuf_core |= cores
                nuf_members.append(cores.ravel())
            c0 += vm.n_cores

    def _pack(members, pad_to):
        n = max(len(members), pad_to, 1)
        onehot = np.zeros((n, n_servers * n_cores), _F32)
        valid = np.zeros(n, bool)
        for i, m in enumerate(members):
            onehot[i] = m
            valid[i] = True
        return onehot, onehot.sum(-1).astype(_F32), valid

    uf_onehot, uf_cores, uf_valid = _pack(uf_members, pad_uf_to)
    nuf_onehot, nuf_cores, nuf_valid = _pack(nuf_members, pad_nuf_to)
    loads = np.zeros(len(uf_valid), _F32)
    loads[:len(uf_loads)] = uf_loads

    def _ids(members, n_slots):
        ids = np.full(n_servers * n_cores, n_slots, np.int32)
        for i, m in enumerate(members):
            ids[m] = i
        return ids

    return FleetLayout(n_servers, n_cores, uf_mask, nuf_core, active,
                       uf_onehot, uf_cores, loads, uf_valid,
                       nuf_onehot, nuf_cores, nuf_valid,
                       _ids(uf_members, len(uf_valid)),
                       _ids(nuf_members, len(nuf_valid)))


def build_uf_traces(layout: FleetLayout, n_steps: int,
                    seed: int | np.ndarray,
                    load_scale: float | np.ndarray = 1.0) -> np.ndarray:
    """Fluctuating interactive offered load (paper Fig. 4 power wiggles).

    An int `seed` gives one chassis' (n_steps, Vu) traces; a (B,) seed
    array gives (B, n_steps, Vu), with `load_scale` a scalar or (B,).
    Each seed's generator draws its valid UF slots' noise in one
    (n_valid, n_steps) normal draw whose rows come in VM order, so the
    draw order per chassis is unchanged: one n_steps draw per VM.
    Padded slots draw nothing and stay 0."""
    seeds = np.asarray(seed)
    wave = 0.12 * np.sin(np.linspace(0, 6 * np.pi, n_steps))
    slow = 0.06 * np.sin(np.linspace(0, 1.5 * np.pi, n_steps))
    idx = np.flatnonzero(layout.uf_valid)
    mean = (layout.uf_loads[idx].astype(np.float64)[:, None] + wave) + slow
    out = np.zeros((seeds.size, n_steps, len(layout.uf_valid)), _F32)
    for b, s in enumerate(seeds.reshape(-1)):
        noise = np.random.default_rng(int(s)).normal(0, 0.03, mean.shape)
        noise += mean
        out[b][:, idx] = np.clip(noise, 0.05, 1.2, out=noise).T
    out *= np.asarray(load_scale, _F32).reshape(-1, 1, 1)
    return out[0] if seeds.ndim == 0 else out


def stack_layouts(layouts: list) -> LayoutArrays:
    """Batch heterogeneous chassis layouts (leading axis B). Pads the
    compact UF-core axis to the largest chassis; VM axes must already
    share shapes (build with pad_uf_to/pad_nuf_to)."""
    ku = max(int((lo.uf_id < len(lo.uf_valid)).sum()) for lo in layouts)
    # `active` collapses to None only if EVERY chassis is fully active
    # (a mix must keep the real masks, not inherit layouts[0]'s)
    active = None if all(lo.active.all() for lo in layouts) \
        else np.stack([lo.active for lo in layouts])
    arrs = [lo.arrays(pad_uf_cores_to=ku)._replace(active=None)
            for lo in layouts]
    return LayoutArrays(*(np.stack(x) if x[0] is not None else None
                          for x in zip(*arrs)))._replace(active=active)


# --- the shared per-step workload/application model -----------------------

def _offered_util(la: LayoutArrays, trace_t, freq, xp):
    """Per-core offered utilization: batch saturates its cores; the
    interactive load rises when cores are slowed (same work, less
    capacity): util = min(1, load / f). Unbatched (one chassis) — jax
    batches via vmap, numpy via the per-chassis loop."""
    pad = xp.concatenate([trace_t, xp.zeros(1, trace_t.dtype)])
    load_core = pad[la.uf_id].reshape(freq.shape)
    util = xp.where(la.uf_mask,
                    xp.minimum(load_core
                               / xp.maximum(freq, _F32(1e-3)), _F32(1.0)),
                    _F32(0.0))
    return xp.where(la.nuf_core, _F32(1.0), util)


def _dot(a, b, xp):
    """``a @ b`` in f32 on every backend: the jnp form asks for
    ``Precision.HIGHEST``, because a TPU's default f32 dot rounds its
    operands to bf16 and these one-hot reductions carry frequencies."""
    if xp is np:
        return a @ b
    import jax
    return xp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _app_update(la: LayoutArrays, trace_t, freq, backlog, freq_sum,
                dt, xp):
    """Fluid-queue UF app + fixed-work NUF app (paper §IV-C). Returns
    updated carries + per-step latency and the minimum NUF core
    frequency. Unbatched (one chassis). The NUF integral carry is the
    raw per-core frequency sum; callers reduce it per-VM after the run."""
    freq_flat = freq.reshape(-1)
    cap = _dot(freq_flat[la.uf_core_idx], la.uf_compact, xp)   # (Vu,)
    lam = trace_t * la.uf_cores
    backlog = xp.clip(backlog + (lam - cap) * _F32(dt), _F32(0.0), cap)
    # closed-loop client pool: bounded in-flight work (backlog <= cap);
    # stationary-queue term capped at rho = 0.9 — sustained overload is
    # carried by the backlog term instead of the M/M/c pole
    meanf = cap / xp.maximum(la.uf_cores, _F32(1.0))
    service = _F32(1.0) / xp.maximum(meanf, _F32(1e-6))
    rho = xp.minimum(lam / xp.maximum(cap, _F32(1e-6)), _F32(0.9))
    latency = service * (_F32(1.0) + rho / (_F32(1.0) - rho) * _F32(0.15)) \
        + backlog / xp.maximum(cap, _F32(1e-6))
    freq_sum = freq_sum + freq_flat
    min_nuf = xp.min(xp.where(la.nuf_core, freq, _F32(F_MAX)),
                     axis=(-2, -1))
    return backlog, freq_sum, latency, min_nuf


def _scalars(budget_w, n_servers: int, min_pstate) -> dict:
    """Per-run control scalars from a chassis budget (inf = uncapped)."""
    budget = np.asarray(budget_w, _F32)
    server_b = budget / _F32(n_servers)
    return {"server_budget": server_b,
            "target": server_b - _F32(ALERT_MARGIN_W),
            "alert": budget * _F32(ALERT_FRACTION),
            "min_pstate": np.asarray(min_pstate, np.int32)}


# --- results --------------------------------------------------------------

@dataclass
class SimResult:
    power_w: np.ndarray                 # (n_steps,) chassis draw
    min_nuf_freq: np.ndarray            # (n_steps,)
    uf_p95_latency: float               # mean across UF VMs
    nuf_slowdown: float                 # mean across NUF VMs (>= 1.0)
    rapl_engaged_frac: float


@dataclass
class FleetResult:
    """Batched over the run axis B (chassis / scenario grid points)."""
    power_w: np.ndarray                 # (B, T)
    min_nuf_freq: np.ndarray            # (B, T)
    uf_latency: np.ndarray              # (B, T, Vu) per-step, padded VMs 0
    alert_frac: np.ndarray              # (B,)
    rapl_engaged_frac: np.ndarray       # (B,)
    uf_p95_latency: np.ndarray          # (B,)
    nuf_slowdown: np.ndarray            # (B,)

    def chassis(self, b: int) -> SimResult:
        return SimResult(self.power_w[b], self.min_nuf_freq[b],
                         float(self.uf_p95_latency[b]),
                         float(self.nuf_slowdown[b]),
                         float(self.rapl_engaged_frac[b]))


def _aggregate(layout_valid, nuf_cores, duration_s, power, min_nuf, lat,
               rapl_cnt, alert, nuf_integ, n_servers) -> FleetResult:
    uf_valid, nuf_valid = layout_valid
    lat = lat * uf_valid.astype(lat.dtype)      # zero padded VM columns
    n_steps = power.shape[-1]
    if uf_valid.any():
        p95 = np.percentile(lat[..., uf_valid], 95, axis=1)   # (B, Vu')
        uf_p95 = p95.mean(-1)
    else:
        uf_p95 = np.zeros(power.shape[0])
    if nuf_valid.any():
        nominal = nuf_cores[nuf_valid] * F_MAX * duration_s
        slow = nominal / np.maximum(nuf_integ[..., nuf_valid], 1e-9)
        nuf_slow = slow.mean(-1)
    else:
        nuf_slow = np.ones(power.shape[0])
    return FleetResult(
        power_w=power, min_nuf_freq=min_nuf, uf_latency=lat,
        alert_frac=alert.mean(-1),
        rapl_engaged_frac=rapl_cnt.sum(-1) / (n_steps * n_servers),
        uf_p95_latency=uf_p95, nuf_slowdown=nuf_slow)


# --- numpy oracle: same arithmetic, Python loop ---------------------------

def _run_numpy_one(cp, la, sc, traces):
    """One chassis, looped over time — the seed's execution model."""
    S, C = la.uf_mask.shape
    st = init_state((), S, C, np)
    rp = RunParams(sc["server_budget"], sc["target"], sc["alert"],
                   sc["min_pstate"], la.uf_mask, la.active)
    n_steps = traces.shape[0]
    backlog = np.zeros(la.uf_cores.shape[0], _F32)
    freq_sum = np.zeros(S * C, _F32)
    power = np.zeros(n_steps, _F32)
    min_nuf = np.zeros(n_steps, _F32)
    lat = np.zeros((n_steps, la.uf_cores.shape[0]), _F32)
    rapl_cnt = np.zeros(n_steps, np.int32)
    alert = np.zeros(n_steps, bool)
    for t in range(n_steps):
        util = _offered_util(la, traces[t], st.freq, np)
        st, outs = fleet_step(cp, rp, st, util, np)
        backlog, freq_sum, lat_t, mn = _app_update(
            la, traces[t], st.freq, backlog, freq_sum, cp.dt, np)
        power[t] = outs.chassis_power_w
        min_nuf[t] = mn
        lat[t] = lat_t
        rapl_cnt[t] = outs.rapl.sum()
        alert[t] = outs.alert
    integ = (freq_sum @ la.nuf_onehot.T) * _F32(cp.dt)
    return power, min_nuf, lat, rapl_cnt, alert, integ


# --- jax engine: scan over time, vmap over chassis ------------------------

def _scan_one(cp, la, sc, traces):
    import jax
    import jax.numpy as jnp
    S, C = la.uf_mask.shape
    rp = RunParams(sc["server_budget"], sc["target"], sc["alert"],
                   sc["min_pstate"], la.uf_mask, la.active)
    st0 = init_state((), S, C, jnp)
    backlog0 = jnp.zeros(la.uf_cores.shape[0], jnp.float32)
    fsum0 = jnp.zeros(S * C, jnp.float32)

    def body(carry, trace_t):
        st, backlog, freq_sum = carry
        util = _offered_util(la, trace_t, st.freq, jnp)
        st2, outs = fleet_step(cp, rp, st, util, jnp)
        backlog, freq_sum, lat_t, mn = _app_update(
            la, trace_t, st2.freq, backlog, freq_sum, cp.dt, jnp)
        ys = (outs.chassis_power_w, mn, lat_t,
              jnp.sum(outs.rapl).astype(jnp.int32), outs.alert)
        return (st2, backlog, freq_sum), ys

    (_, _, freq_sum), ys = jax.lax.scan(body, (st0, backlog0, fsum0),
                                        traces, unroll=8)
    integ = _dot(freq_sum, la.nuf_onehot.T, jnp) * jnp.float32(cp.dt)
    return ys + (integ,)


_ENGINE_CACHE: dict = {}


def _jax_engine(cp: ControlParams, shared_layout: bool):
    """jit(vmap(scan)) with a stable cache key so recompilation only
    happens per (ControlParams, layout-sharing, shape) signature."""
    key = (cp, shared_layout)
    if key not in _ENGINE_CACHE:
        import jax
        ax = None if shared_layout else 0

        @jax.jit
        def engine(la, sc, traces):
            return jax.vmap(partial(_scan_one, cp),
                            in_axes=(ax, 0, 0))(la, sc, traces)
        _ENGINE_CACHE[key] = engine
    return _ENGINE_CACHE[key]


# --- public API -----------------------------------------------------------

def run_fleet(specs: list, budgets_w, mode: str,
              duration_s: float = 600.0, seed=0,
              model: ServerPowerModel | None = None,
              backend: str = "jax", load_scale=1.0, min_pstate=None,
              layout: FleetLayout | None = None,
              traces: np.ndarray | None = None) -> FleetResult:
    """Simulate a fleet of identical-layout chassis under per-chassis
    budgets. `budgets_w`: None (uncapped), scalar, or (B,) array —
    the run axis. `seed`: int (all chassis share one trace draw) or
    (B,) array (independent chassis). Returns batched FleetResult.
    """
    model = model or ServerPowerModel()
    cp = ControlParams.from_model(model, mode=mode)
    layout = layout or build_layout(specs)
    n_steps = int(duration_s / POLL_INTERVAL_S)

    budgets = np.asarray(
        [np.inf] if budgets_w is None else budgets_w, _F32).reshape(-1)
    budgets = np.where(np.isfinite(budgets), budgets, np.inf)
    n_runs = len(budgets)

    if traces is None:
        seeds = np.broadcast_to(np.asarray(seed), (n_runs,))
        scales = np.broadcast_to(np.asarray(load_scale, _F32), (n_runs,))
        if np.all(seeds == seeds[0]):
            base = build_uf_traces(layout, n_steps, int(seeds[0]))
            traces = base[None] * scales[:, None, None]
        else:
            traces = build_uf_traces(layout, n_steps, seeds, scales)
    traces = np.asarray(traces, _F32)
    if traces.ndim == 2:
        traces = np.broadcast_to(traces[None], (n_runs,) + traces.shape)

    minp = N_PSTATES - 1 if min_pstate is None else min_pstate
    sc = _scalars(budgets, layout.n_servers,
                  np.broadcast_to(np.asarray(minp, np.int32), (n_runs,)))
    la = layout.arrays()

    if backend == "numpy":
        outs = [_run_numpy_one(cp, la,
                               {k: v[b] for k, v in sc.items()},
                               traces[b])
                for b in range(n_runs)]
        power, min_nuf, lat, rapl_cnt, alert, integ = \
            (np.stack(x) for x in zip(*outs))
    else:
        engine = _jax_engine(cp, shared_layout=True)
        power, min_nuf, lat, rapl_cnt, alert, integ = \
            (np.asarray(x) for x in engine(la, sc, traces))
    return _aggregate((layout.uf_valid, layout.nuf_valid),
                      layout.nuf_cores, duration_s, power, min_nuf, lat,
                      rapl_cnt, alert, integ, layout.n_servers)


def run_fleet_layouts(layouts_arrays, uf_valid, nuf_valid, nuf_cores,
                      budgets_w, mode: str, traces,
                      model: ServerPowerModel | None = None,
                      duration_s: float | None = None,
                      backend: str = "jax") -> FleetResult:
    """Heterogeneous fleet: every chassis brings its own (padded,
    shape-identical) layout arrays — batched with leading axis B. Used
    by the scheduler simulation to evaluate the capping dynamics of the
    placements it actually produced."""
    model = model or ServerPowerModel()
    cp = ControlParams.from_model(model, mode=mode)
    n_runs, n_steps = traces.shape[0], traces.shape[1]
    layouts_arrays = LayoutArrays(*layouts_arrays)
    n_servers = layouts_arrays.uf_mask.shape[1]
    if duration_s is None:
        duration_s = n_steps * POLL_INTERVAL_S
    budgets = np.asarray(budgets_w, _F32).reshape(-1)
    minp = np.full(n_runs, N_PSTATES - 1, np.int32)
    sc = _scalars(np.broadcast_to(budgets, (n_runs,)), n_servers, minp)
    traces = np.asarray(traces, _F32)
    if backend == "numpy":
        outs = [_run_numpy_one(
                    cp, LayoutArrays(*(None if a is None else a[b]
                                       for a in layouts_arrays)),
                    {k: v[b] for k, v in sc.items()}, traces[b])
                for b in range(n_runs)]
        power, min_nuf, lat, rapl_cnt, alert, integ = \
            (np.stack(x) for x in zip(*outs))
    else:
        engine = _jax_engine(cp, shared_layout=False)
        power, min_nuf, lat, rapl_cnt, alert, integ = \
            (np.asarray(x) for x in engine(layouts_arrays, sc, traces))
    # per-chassis VM validity differs: aggregate row-wise
    lat = lat * uf_valid[:, None, :].astype(lat.dtype)
    uf_p95 = np.zeros(n_runs)
    nuf_slow = np.ones(n_runs)
    for b in range(n_runs):
        if uf_valid[b].any():
            uf_p95[b] = np.percentile(lat[b][:, uf_valid[b]], 95,
                                      axis=0).mean()
        if nuf_valid[b].any():
            nominal = nuf_cores[b][nuf_valid[b]] * F_MAX * duration_s
            nuf_slow[b] = (nominal / np.maximum(
                integ[b][nuf_valid[b]], 1e-9)).mean()
    return FleetResult(power, min_nuf, lat, alert.mean(-1),
                       rapl_cnt.sum(-1) / (n_steps * n_servers),
                       uf_p95, nuf_slow)


# --- scenario sweeps (Table IV-style frontiers) ---------------------------

def fmin_to_pstate(fmin: float) -> int:
    """Nearest p-state index for a frequency floor (FREQ_TABLE is
    descending f_max..f_min)."""
    return int(np.argmin(np.abs(FREQ_TABLE - np.float32(fmin))))


def sweep_scenarios(specs: list, budgets_w, load_scales=(1.0,),
                    fmin_nuf=(F_MIN,), mode: str = "per_vm",
                    duration_s: float = 120.0, seed: int = 0,
                    model: ServerPowerModel | None = None,
                    backend: str = "jax",
                    include_uncapped: bool = True) -> dict:
    """One compiled call over the (budget x load-scale x NUF-floor)
    grid. Returns metric arrays of shape (n_budgets[+1], n_loads,
    n_floors); index 0 of the budget axis is the uncapped baseline when
    `include_uncapped` (for latency-impact ratios)."""
    budgets = list(np.asarray(budgets_w, np.float64).reshape(-1))
    if include_uncapped:
        budgets = [np.inf] + budgets
    shape = (len(budgets), len(load_scales), len(fmin_nuf))
    bb, ll, ff = np.meshgrid(
        np.asarray(budgets, _F32), np.asarray(load_scales, _F32),
        np.asarray([fmin_to_pstate(f) for f in fmin_nuf], np.int32),
        indexing="ij")
    res = run_fleet(specs, bb.ravel(), mode, duration_s, seed, model,
                    backend, load_scale=ll.ravel(),
                    min_pstate=ff.ravel())
    out = {"budgets_w": np.asarray(budgets),
           "load_scales": np.asarray(load_scales),
           "fmin_nuf": np.asarray(fmin_nuf)}
    for name in ("uf_p95_latency", "nuf_slowdown", "rapl_engaged_frac",
                 "alert_frac"):
        out[name] = getattr(res, name).reshape(shape)
    out["power_max_w"] = res.power_w.max(-1).reshape(shape)
    if include_uncapped:
        base = out["uf_p95_latency"][:1]
        out["uf_latency_ratio"] = out["uf_p95_latency"] \
            / np.maximum(base, 1e-9)
    return out


def frontier(sweep: dict, provisioned_w: float,
             max_uf_latency_ratio: float = 1.05,
             max_rapl_frac: float = 0.001) -> dict:
    """Table IV-style frontier: for each (load-scale, NUF-floor) cell,
    the lowest budget whose measured UF impact and RAPL engagement stay
    within tolerance, and the recovered provisioned-power fraction."""
    if "uf_latency_ratio" not in sweep:
        raise ValueError("sweep must include the uncapped baseline")
    ok = (sweep["uf_latency_ratio"] <= max_uf_latency_ratio) \
        & (sweep["rapl_engaged_frac"] <= max_rapl_frac)
    budgets = sweep["budgets_w"]                       # descending walk
    best = np.full(ok.shape[1:], np.inf)
    for i in range(ok.shape[0]):
        best = np.where(ok[i] & np.isfinite(budgets[i]),
                        np.minimum(best, budgets[i]), best)
    feasible = np.isfinite(best)
    oversub = np.where(feasible, 1.0 - best / provisioned_w, 0.0)
    return {"budget_w": np.where(feasible, best, provisioned_w),
            "oversubscription": oversub, "feasible": feasible}
