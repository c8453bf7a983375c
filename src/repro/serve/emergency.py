"""Online power-emergency control plane (DESIGN.md §12, docs/emergency.md).

The serve pipeline admits against *projected* peak draw
(`serve.admission`); emergencies are what happens when reality beats
the projection — a chassis' measured draw trips the protective-capping
alarm and watts must come off *now*, with minimum impact to critical
workloads (the paper's §V headline property). This module is the
batched online twin of the chassis-manager + per-VM-controller +
RAPL-backstop stack of `repro.core.capping`:

  * **Alarms** — `ChassisManager` semantics in bulk: every chassis of
    a shard is polled in one compare against ``alert_fraction *
    chassis_budget_w`` (`EmergencyConfig.alert_w`).
  * **Criticality-aware apportionment** — the required cut
    (sampled draw minus the capped target) is apportioned across
    criticality levels lowest-first by
    `repro.core.capping.apportion_watts`: non-critical dynamic draw is
    shaved down to its frequency floor before critical VMs lose a
    hertz, critical levels are capped to *their* (higher) floor next,
    and only a cut no floor can absorb engages the RAPL backstop
    (all cores to f_min, criticality-blind). Unlike the in-band
    feedback loop of `core.capping.PerVMController.step`, the serve
    plane *knows* each level's committed dynamic draw from the
    placement aggregates, so the controller is one-shot
    model-predictive: the post-action draw lands at or under the
    target in the same scan that raised the alarm.
  * **Hysteresis** — an alarmed chassis re-apportions every sample; a
    chassis whose draw falls back below the alert threshold holds its
    cap for `lift_after_s` (the paper's 30 s lift delay) and then
    restores nominal frequency.
  * **Dwell** — `capped_s` tracks how long each chassis has been
    continuously capped; `mitigation_due` flags chassis whose
    *critical* levels have been throttled past `dwell_s` — the signal
    `repro.serve.mitigation` turns into a migration plan.

Everything is branchless, fixed-shape, and xp-generic: the numpy call
is the oracle, `jax.vmap` batches it per shard on one device, and
`jax.shard_map` runs one copy per mesh device
(`repro.serve.sharding.apply_caps_sharded`) — all three asserted equal
in `tests/test_serve_emergency.py`. Power samples reach the plane as
the third stream-event kind (`repro.serve.ingest.CAPPING`), so
emergencies merge deterministically with arrivals and departures
across ingest hosts.

Observability (DESIGN.md §17): every sweep's in-scan counters
(alarms, samples, demanded/leftover watts, per-level cuts) are
scan-carried *outputs* the pipeline folds host-side into the metrics
registry, the windowed aggregates, the SLO burn-rate monitor
(critical throttled-seconds and alarm-rate budgets), and — when a
sweep raises alarms — a flight-recorder incident marker with the
surrounding event stream (`obs.recorder`).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple

import numpy as np

from repro.core.capping import (ChassisManager, RaplController,
                                apportion_watts)
from repro.core.fleet_dynamics import (ALERT_FRACTION, ALERT_MARGIN_W,
                                       FREQ_TABLE, LIFT_AFTER_S)
from repro.core.power_model import (F_MAX, N_PSTATES, ServerPowerModel,
                                    dyn_scale, idle_power)
from repro.core.rounding import div

#: Criticality levels, in apportionment priority order: level 0
#: (non-user-facing) absorbs the cut first, level 1 (user-facing /
#: critical) only when level 0's floor is insufficient.
CRIT_NUF = 0
CRIT_UF = 1
N_LEVELS = 2

#: Default frequency floor of the *critical* level: p-state 5 = 0.75
#: f_max — critical VMs may be politely trimmed this far by the
#: criticality-aware stage; anything deeper takes the RAPL backstop.
UF_FLOOR_PSTATE = 5

_TOL_W = 1e-6          # leftover below this is float fuzz, not a deficit


@dataclass(frozen=True)
class EmergencyConfig:
    """Static (hashable) knobs of the power-emergency plane — safe as a
    jit static argument, like `core.fleet_dynamics.ControlParams`.

    `floors` is the per-criticality-level p-state floor in priority
    order: how deep the criticality-aware stage may cap each level
    before the leftover falls through to the RAPL backstop. The default
    lets non-critical VMs reach f_min while critical VMs are never
    trimmed below 0.75 f_max without RAPL."""
    chassis_budget_w: float
    alert_fraction: float = ALERT_FRACTION
    target_margin_w: float = ALERT_MARGIN_W
    floors: tuple = (N_PSTATES - 1, UF_FLOOR_PSTATE)
    lift_after_s: float = LIFT_AFTER_S
    dwell_s: float = 30.0
    criticality_blind: bool = False
    blades_per_chassis: int = 12
    p_dyn_per_core: float = ServerPowerModel().p_dyn_per_core
    idle_w_per_server: float = float(idle_power(F_MAX))

    @property
    def alert_w(self) -> float:
        """Protective-capping alarm threshold (watts)."""
        return self.chassis_budget_w * self.alert_fraction

    @property
    def target_w(self) -> float:
        """Draw the apportionment steers an alarmed chassis to."""
        return self.chassis_budget_w - self.target_margin_w

    @property
    def static_w(self) -> float:
        """Frequency-independent chassis floor: every blade's idle
        draw at nominal frequency (the admission model's intercept)."""
        return self.blades_per_chassis * self.idle_w_per_server

    def manager(self) -> ChassisManager:
        """The equivalent per-chassis `core.capping.ChassisManager`
        (the oracle tests poll through it)."""
        return ChassisManager(self.chassis_budget_w, self.alert_fraction,
                              self.target_margin_w)

    @classmethod
    def from_model(cls, chassis_budget_w: float,
                   model: ServerPowerModel | None = None,
                   **kw) -> "EmergencyConfig":
        """Build a config calibrated to a `ServerPowerModel`."""
        model = model or ServerPowerModel()
        return cls(chassis_budget_w=chassis_budget_w,
                   p_dyn_per_core=model.p_dyn_per_core, **kw)


class EmergencyState(NamedTuple):
    """Per-chassis controller state; all fixed-shape, batchable with
    leading dims (the sharded plane carries a leading shard axis)."""
    pstate: Any        # (..., C, L) i32 — per-level uniform p-state
    rapl: Any          # (..., C) bool — RAPL backstop engaged
    capped_s: Any      # (..., C) f32 — continuous seconds capped (dwell)
    clear_s: Any       # (..., C) f32 — seconds since the alarm cleared
    throttled_s: Any   # (..., C, L) f32 — cumulative per-level
    last_t: Any        # (..., C) — stamp of the last applied sample


class EmergencyOutputs(NamedTuple):
    """Per-sample observables of one emergency scan."""
    power_w: Any       # (..., C) — offered (uncapped) draw this sample
    power_after_w: Any  # (..., C) — draw at the post-action settings
    alarm: Any         # (..., C) bool
    cut_w: Any         # (..., C) — required reduction past the target
    leftover_w: Any    # (..., C) — cut no floor absorbed (RAPL trigger)
    cut_by_level_w: Any  # (..., C, L) — watts removed per crit level


def init_emergency(n_chassis: int, batch_shape=(), xp=np,
                   dtype=np.float32) -> EmergencyState:
    """Uncapped initial emergency state — nominal frequency everywhere,
    no alarms ever seen (``last_t = -inf``)."""
    shape_c = tuple(batch_shape) + (n_chassis,)
    shape_l = shape_c + (N_LEVELS,)
    return EmergencyState(
        pstate=xp.zeros(shape_l, np.int32),
        rapl=xp.zeros(shape_c, bool),
        capped_s=xp.zeros(shape_c, dtype),
        clear_s=xp.full(shape_c, np.inf, dtype),
        throttled_s=xp.zeros(shape_l, dtype),
        last_t=xp.full(shape_c, -np.inf, dtype))


def chassis_rho_levels(gamma_nuf, gamma_uf, chassis_servers, xp=np):
    """(C, L) committed ``sum(p95*cores)`` per chassis per criticality
    level, gathered from the per-server placement aggregates through
    the (C, K) chassis->servers table — the emergency plane's view of
    what is drawing power where. Level order is apportionment priority
    (non-critical first)."""
    nuf = xp.sum(gamma_nuf[chassis_servers], axis=-1)
    uf = xp.sum(gamma_uf[chassis_servers], axis=-1)
    return xp.stack([nuf, uf], axis=-1)


def sampled_power(cfg: EmergencyConfig, rho_lv, util, pstate, rapl,
                  xp=np):
    """Chassis draw at the given control settings under the admission
    power model: ``static + p_dyn * sum_l rho_l * util * g(f_l)``,
    with RAPL-engaged chassis at f_min on every level."""
    dtype = xp.asarray(rho_lv).dtype
    gtab = xp.asarray(dyn_scale(FREQ_TABLE), dtype)
    g = xp.where(xp.asarray(rapl)[..., None],
                 gtab[RaplController.backstop_pstate()], gtab[pstate])
    util = xp.asarray(util, dtype)
    dyn = cfg.p_dyn_per_core * rho_lv * util[..., None]
    return cfg.static_w + xp.sum(dyn * g, axis=-1)


def util_from_power(cfg: EmergencyConfig, rho_lv, power_w, xp=np):
    """Implied utilization of the committed P95 behind a sampled
    *uncapped* draw: ``(power - static) / (p_dyn * sum_l rho_l)``,
    clipped at 0 (a draw below the static floor reads as idle) with a
    zero-commitment guard (an empty chassis implies util 0, not a
    division by its zero rho)."""
    rho = xp.sum(rho_lv, axis=-1)
    dyn = xp.maximum(xp.asarray(power_w) - cfg.static_w, 0)
    return xp.where(rho > 0,
                    div(dyn, cfg.p_dyn_per_core
                        * xp.where(rho > 0, rho, 1), xp),
                    0.0)


def emergency_step(cfg: EmergencyConfig, st: EmergencyState, rho_lv,
                   util, t, xp=np):
    """One emergency scan over a (batch of) chassis.

    rho_lv: (..., C, L) committed p95*cores per level
    (`chassis_rho_levels`); util: scalar or (..., C) utilization sample
    scaling the commitment into an offered draw; `t`: sample stamp
    (scalar or (..., C)) — elapsed time against `last_t` accrues the
    dwell clock and per-level throttled-seconds at the settings that
    held over the interval.

    Returns ``(new_state, EmergencyOutputs)``. Branchless; identical
    under numpy and jnp (the numpy call is the oracle the jax
    executions are asserted against)."""
    rho_lv = xp.asarray(rho_lv)
    dtype = rho_lv.dtype
    util = xp.asarray(util, dtype)
    dyn_full = cfg.p_dyn_per_core * rho_lv * util[..., None]
    p_full = cfg.static_w + xp.sum(dyn_full, axis=-1)     # (..., C)
    alarm = p_full >= dtype.type(cfg.alert_w)

    t = xp.asarray(t, st.last_t.dtype)
    dt = xp.where(xp.isfinite(st.last_t),
                  xp.maximum(t - st.last_t, 0), 0).astype(dtype)

    # accrue dwell + throttled-seconds at the settings that held over
    # [last_t, t)
    was_thr = (st.pstate > 0) | st.rapl[..., None]        # (..., C, L)
    throttled_s = st.throttled_s + dt[..., None] * was_thr
    was_capped = xp.any(was_thr, axis=-1)
    capped_accum = (st.capped_s + dt) * was_capped
    clear_accum = xp.where(alarm, 0,
                           xp.where(was_capped, st.clear_s + dt,
                                    dtype.type(np.inf)))
    lift = was_capped & ~alarm \
        & (clear_accum >= dtype.type(cfg.lift_after_s))
    hold = was_capped & ~alarm & ~lift

    cut = xp.maximum(p_full - dtype.type(cfg.target_w), 0)
    pst_new, _, leftover = apportion_watts(
        cut, dyn_full, cfg.floors, xp, blind=cfg.criticality_blind)
    pstate = xp.where(alarm[..., None], pst_new,
                      xp.where(hold[..., None], st.pstate, 0))
    rapl = xp.where(alarm, leftover > _TOL_W,
                    xp.where(hold, st.rapl, False))

    now_capped = xp.any(pstate > 0, axis=-1) | rapl
    capped_s = xp.where(now_capped, capped_accum, 0).astype(dtype)
    clear_s = xp.where(alarm, 0,
                       xp.where(now_capped, clear_accum,
                                dtype.type(np.inf))).astype(dtype)
    last_t = xp.broadcast_to(t, st.last_t.shape).astype(st.last_t.dtype)

    p_after = sampled_power(cfg, rho_lv, util, pstate, rapl, xp)
    # per-level watts removed at the post-action settings — the same
    # g as `sampled_power` uses, so cut_by_level decomposes the
    # (p_full - p_after) reduction by criticality level
    gtab = xp.asarray(dyn_scale(FREQ_TABLE), dtype)
    g = xp.where(rapl[..., None],
                 gtab[RaplController.backstop_pstate()], gtab[pstate])
    cut_lv = dyn_full * (1 - g)
    st2 = EmergencyState(pstate, rapl, capped_s, clear_s,
                         throttled_s.astype(dtype), last_t)
    return st2, EmergencyOutputs(p_full, p_after, alarm, cut, leftover,
                                 cut_lv)


def masked_step(cfg: EmergencyConfig, st: EmergencyState, rho_lv,
                power_w, mask, t, xp=np):
    """`emergency_step` driven by *sampled draws* for a subset of
    chassis — the dense, vmappable form the stream-event path uses.

    power_w/mask/t: (..., C) — only ``mask`` rows carry a fresh sample
    (their utilization is implied via `util_from_power`); unmasked
    chassis carry their state forward untouched, including their
    clocks (their elapsed time accrues when they are next sampled)."""
    util = util_from_power(cfg, rho_lv, power_w, xp)
    st2, out = emergency_step(cfg, st, rho_lv, util, t, xp)

    def sel(new, old):
        m = mask[..., None] if new.ndim == mask.ndim + 1 else mask
        return xp.where(m, new, old)

    st3 = EmergencyState(*(sel(n, xp.asarray(o))
                           for n, o in zip(st2, st)))
    zero = xp.zeros_like(out.power_w)
    return st3, EmergencyOutputs(
        xp.where(mask, out.power_w, zero),
        xp.where(mask, out.power_after_w, zero),
        mask & out.alarm,
        xp.where(mask, out.cut_w, zero),
        xp.where(mask, out.leftover_w, zero),
        xp.where(mask[..., None], out.cut_by_level_w,
                 zero[..., None]))


def scatter_samples(n_chassis: int, chassis, power_w, t, xp=np,
                    dtype=np.float32):
    """Densify one sparse sample batch: (B,) chassis ids (assumed
    unique — the pipeline splits duplicate-bearing windows) with their
    draws and stamps become the (C,) ``(power_w, mask, t)`` operands of
    `masked_step`."""
    chassis = np.asarray(chassis, np.int64)
    if xp is np:
        pw = np.zeros(n_chassis, dtype)
        mask = np.zeros(n_chassis, bool)
        ts = np.zeros(n_chassis, np.float64)
        pw[chassis] = power_w
        mask[chassis] = True
        ts[chassis] = t
        return pw, mask, ts
    pw = xp.zeros(n_chassis, dtype).at[chassis].set(
        xp.asarray(power_w, dtype))
    mask = xp.zeros(n_chassis, bool).at[chassis].set(True)
    ts = xp.zeros(n_chassis, dtype).at[chassis].set(xp.asarray(t, dtype))
    return pw, mask, ts


def mitigation_due(cfg: EmergencyConfig, st: EmergencyState, xp=np):
    """(..., C) bool — chassis whose cap has dwelled past
    ``cfg.dwell_s`` with the *critical* level throttled (a polite NUF
    cap that clears fast never migrates anyone). The trigger
    `repro.serve.mitigation.plan_migrations` consumes."""
    crit_thr = (st.pstate[..., CRIT_UF] > 0) | st.rapl
    return crit_thr & (st.capped_s >= cfg.dwell_s)


def reset_dwell(st: EmergencyState, chassis_mask, xp=np) -> EmergencyState:
    """Zero the dwell clock of the masked chassis — called after a
    migration plan is emitted for them, so one persistent emergency
    yields one plan per dwell period, not one per sample."""
    return st._replace(
        capped_s=xp.where(chassis_mask, 0, st.capped_s))


def throttled_by_level(st: EmergencyState) -> np.ndarray:
    """(L,) total throttled-seconds per criticality level, summed over
    chassis (and any leading batch dims) — the paper's Table-4-style
    impact axis: index `CRIT_UF` is the critical number that the
    criticality-aware apportionment keeps low."""
    return np.asarray(st.throttled_s).reshape(-1, N_LEVELS).sum(0)
