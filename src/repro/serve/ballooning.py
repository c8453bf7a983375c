"""Memory ballooning: the middle rung of the mitigation ladder
(cap -> balloon -> migrate; DESIGN.md §16, docs/resources.md).

When a chassis alarms, the emergency plane first apportions the watt
cut across frequency floors (`serve.emergency`). If the cut exceeds
what the *non-critical* floor can absorb, the overflow throttles
critical VMs and — after a dwell — triggers live migration
(`serve.mitigation`). Both are expensive; migration doubly so. But a
joint (watts, cores, GB) ledger knows something the watt-only plane
did not: how much reclaimable memory the chassis' non-user-facing VMs
hold. Ballooning that memory out powers its DRAM down, shaving
``w_per_gb`` watts per reclaimed GB *without touching any critical
core* — so the rung fires exactly when the NUF frequency floor is
insufficient but on-chassis memory headroom exists, and the ladder
becomes: cap NUF, then balloon NUF memory, and only then throttle
UF / migrate.

How much to reclaim — closed form. The emergency plane's sampled
power model is affine in utilization: ``p = static + dyn`` with
``dyn = p_dyn_per_core * sum(rho_lv) * util``. Absorbing ``A`` watts
of DRAM rescales the inferred utilization (and with it every level's
full-frequency draw) by ``s = (dyn - A) / dyn``. The critical level
stays untouched iff the cut fits inside the NUF floor's capacity at
the *adjusted* utilization:

    cut - A <= s * cap_nuf,   cap_nuf = dyn_nuf * frac(floor_nuf)

which solves to the demand

    A* = (cut - cap_nuf) * dyn / (dyn - cap_nuf)        (when > 0)

`balloon_step` grabs ``min(A*/w_per_gb, headroom)`` GB where
``headroom = reclaim_frac * mem_nuf - ballooned``; a fully served
demand provably zeroes both the UF p-state and the RAPL leftover of
the subsequent `emergency.masked_step`, which is the benchmarked
ladder effect (`benchmarks/serve_resources.py`): fewer critical
throttled-seconds and fewer `mitigation_due` chassis, hence fewer
migrations, at identical watt budgets.

Same kernel discipline as `serve.emergency`: every function is
branchless and xp-generic — the simulator runs the numpy call as its
own oracle and asserts the jitted jnp twin bit-equal on every scan
(`tests/test_ballooning.py`, x64).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple

import numpy as np

from repro.core.capping import reducible_fracs
from repro.core.rounding import div
from repro.serve.emergency import (EmergencyConfig, _TOL_W,
                                   util_from_power)

#: Leftover/demand below this is float fuzz, not a deficit (the
#: emergency plane's own tolerance — one ladder, one epsilon).
TOL_W = _TOL_W


@dataclass(frozen=True)
class BallooningConfig:
    """Static knobs of the ballooning rung (jit-static, hashable).

    w_per_gb:     DRAM power per resident GB — what powering a
                  ballooned-out GB down gives back. Default models
                  ~3 W per 8 GB DIMM rank.
    reclaim_frac: fraction of a chassis' NUF-committed GB the balloon
                  driver may take (guest working sets keep the rest).
    """
    w_per_gb: float = 0.375
    reclaim_frac: float = 0.5


class BalloonState(NamedTuple):
    """Carried ballooning state; leading batch axes mirror
    `EmergencyState` (vmapped sweeps share the layout)."""
    ballooned_gb: Any    # (..., C) currently reclaimed GB per chassis


class BalloonOutputs(NamedTuple):
    """Per-step outputs of `balloon_step` (all (..., C))."""
    power_adj_w: Any     # sample with DRAM absorption subtracted —
                         # what feeds `emergency.masked_step`
    reclaimed_gb: Any    # newly ballooned-out GB this step
    released_gb: Any     # GB handed back this step (alarm cleared)
    absorbed_w: Any      # total DRAM watts absorbed this step
    inflated: Any        # bool: rung fired on this chassis


def init_ballooning(n_chassis: int, batch_shape: tuple = (),
                    xp=np, dtype=np.float64) -> BalloonState:
    """All-deflated state (no memory ballooned out)."""
    return BalloonState(
        ballooned_gb=xp.zeros(batch_shape + (n_chassis,), dtype))


def balloon_demand_w(ecfg: EmergencyConfig, rho_lv, power_w, xp=np):
    """(alarm, demand) of the closed form above, from a raw power
    sample: ``alarm`` (..., C) bool mirrors `emergency_step`'s alarm
    predicate; ``demand`` (..., C) is the DRAM watt absorption that
    keeps the cut inside the NUF floor (0 where the floor already
    suffices, or where no alarm)."""
    rho_lv = xp.asarray(rho_lv)
    dtype = rho_lv.dtype
    util = util_from_power(ecfg, rho_lv, power_w, xp=xp)
    dyn_full = dtype.type(ecfg.p_dyn_per_core) * rho_lv * util[..., None]
    dyn = xp.sum(dyn_full, axis=-1)                       # (..., C)
    p_full = dtype.type(ecfg.static_w) + dyn
    alarm = p_full >= dtype.type(ecfg.alert_w)
    cut = xp.maximum(p_full - dtype.type(ecfg.target_w), 0)
    frac_nuf = dtype.type(float(reducible_fracs()[ecfg.floors[0]]))
    cap_nuf = dyn_full[..., 0] * frac_nuf
    deficit = xp.maximum(cut - cap_nuf, 0)
    denom = xp.maximum(dyn - cap_nuf, dtype.type(TOL_W))
    # +TOL_W margin so the served demand lands the adjusted cut
    # strictly inside the NUF capacity — exact equality would let
    # float rounding tip an epsilon share onto the critical level.
    demand = xp.where(alarm & (deficit > dtype.type(TOL_W)),
                      div((deficit + dtype.type(TOL_W)) * dyn, denom, xp),
                      dtype.type(0))
    return alarm, demand


def balloon_step(cfg: BallooningConfig, ecfg: EmergencyConfig,
                 st: BalloonState, rho_lv, power_w, mem_nuf_gb,
                 mask, xp=np) -> tuple[BalloonState, BalloonOutputs]:
    """One ballooning sweep over the chassis that sampled this step.

    rho_lv:     (..., C, L) per-criticality rho levels
                (`emergency.chassis_rho_levels`).
    power_w:    (..., C) raw sampled draws — DRAM-blind, i.e. NOT yet
                credited for standing balloons (the simulator's
                `sampled_power` knows nothing of DRAM; this step owns
                the correction).
    mem_nuf_gb: (..., C) GB currently committed to NUF VMs
                (`DeviceClusterState.mem_nuf`).
    mask:       (..., C) bool — chassis that sampled this step;
                unmasked chassis keep their state bit-for-bit and
                pass their power through untouched.

    The step first credits the standing balloon against the sample
    (``p0 = power - w_per_gb * ballooned``), evaluates alarm/demand
    on that corrected draw, inflates up to the headroom on alarmed
    chassis and schedules a full deflate on cleared ones (the
    returned GB re-powers its DRAM *next* sample, so this step's
    ``power_adj_w`` still credits it). Feed ``power_adj_w`` to
    `emergency.masked_step` in place of the raw sample.
    """
    ballooned = xp.asarray(st.ballooned_gb)
    dtype = ballooned.dtype
    w_per_gb = dtype.type(cfg.w_per_gb)
    mask = xp.asarray(mask)
    power_w = xp.asarray(power_w, dtype)

    standing_w = w_per_gb * ballooned
    p0 = power_w - standing_w
    alarm, demand_w = balloon_demand_w(ecfg, rho_lv, p0, xp=xp)

    headroom = xp.maximum(
        dtype.type(cfg.reclaim_frac) * xp.asarray(mem_nuf_gb, dtype)
        - ballooned, 0)
    want_gb = div(demand_w, w_per_gb, xp)
    grab = xp.where(mask & alarm, xp.minimum(want_gb, headroom),
                    dtype.type(0))
    release = xp.where(mask & ~alarm, ballooned, dtype.type(0))
    ballooned_new = ballooned + grab - release

    absorbed = xp.where(mask, standing_w + w_per_gb * grab,
                        dtype.type(0))
    power_adj = power_w - absorbed
    out = BalloonOutputs(power_adj_w=power_adj, reclaimed_gb=grab,
                         released_gb=release, absorbed_w=absorbed,
                         inflated=grab > dtype.type(TOL_W))
    return BalloonState(ballooned_gb=ballooned_new), out


def total_ballooned_gb(st: BalloonState) -> float:
    """Fleet-wide GB currently ballooned out (host-side reduction)."""
    return float(np.asarray(st.ballooned_gb).sum())
