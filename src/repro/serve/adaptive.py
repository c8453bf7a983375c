"""Closed-loop adaptive oversubscription (DESIGN.md §15, docs/adaptive.md).

The paper picks its oversubscription ratio *offline* from historical
utilization percentiles (§IV, Table 4); this module closes the loop
online. Every chassis power sample flowing through the ingest stream
(the CAPPING event kind of `repro.serve.ingest`) also lands in a
rolling per-chassis utilization window, and a vectorized *stability
assesser* scores every window in-scan:

  * **percentile spread** — the distance between a low and a high
    percentile of the window (ScroogeVM's percentile assesser): a
    tight band means the chassis' draw is predictable;
  * **sign-change rate** — the fraction of consecutive utilization
    deltas that reverse direction (a GMR-style oscillation score):
    few reversals mean the window is trending, not thrashing.

A chassis whose window is long enough (``min_history``), whose spread
and flip-rate are under their thresholds, and whose *latest* sample is
below the ``hot_util`` level is **stable**. The fleet-level controller
is then ScroogeVM's ratchet-up/back-off-fast rule:

  * when the stable fraction of known chassis reaches
    ``ratchet_quorum`` and nothing is hot, the oversubscription ratio
    creeps up by ``step_up``;
  * when any chassis runs hot or the stable fraction drops below
    ``backoff_quorum``, the ratio collapses by ``step_down`` (several
    times the up-step);
  * otherwise it holds. The ratio is clamped to
    ``[ratio_min, ratio_max]`` and **starts at 1.0 — no history, no
    oversubscription**.

The ratio widens or shrinks the effective watt budget between batches:
it scales the per-chassis admission ceiling (`ServePipeline.rho_cap`)
and, sharded, retargets the free `rho_pool` token allowance
(`retarget_pool`). Tokens already committed to placed VMs are **never
revoked** — a shrink only drains the free pool (floored at zero), so
the reserve/commit conservation invariants of DESIGN.md §10 hold
unchanged; the emergency plane (`serve.emergency`) remains the safety
net for commitment the controller can no longer cover.

Everything is branchless, fixed-shape, and xp-generic with leading
batch dims (the sharded plane carries a leading shard axis): the
numpy call is the oracle, and the sim backends
(`sim.scheduler_sim.simulate` with ``SimSpec(adaptive=...)``) assert
the compiled
jnp twin bit-identical on every scan. Controller decisions export
through the observability plane (`adaptive_ratio` gauge,
`adaptive_backoff_total` counter, `obs.audit.AdaptiveTrail` reason
rows).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple

import numpy as np

from repro.core.power_model import F_MAX, ServerPowerModel, idle_power
from repro.core.rounding import div

__all__ = [
    "AdaptiveConfig", "AdaptiveState", "AdaptiveOutputs",
    "init_adaptive", "adaptive_step", "offered_power",
    "retarget_pool", "gate_ratio_on_stale", "decision_reason",
    "REASON_NAMES",
]

#: Human names of the controller decision reasons recorded into the
#: `obs.audit.AdaptiveTrail` ring (`decision_reason` computes them).
REASON_NAMES = (
    "hold_no_history",      # 0: no chassis has enough window yet
    "hold_band",            # 1: stable frac between the quorums
    "ratchet_quorum",       # 2: stable quorum met -> step up
    "ratchet_ceiling",      # 3: quorum met but ratio pinned at max
    "backoff_hot",          # 4: a chassis ran hot -> step down fast
    "backoff_quorum",       # 5: stable frac under the floor quorum
    "backoff_floor",        # 6: back-off demanded but ratio at min
)


@dataclass(frozen=True)
class AdaptiveConfig:
    """Static (hashable) knobs of the adaptive-ratio controller — safe
    as a jit static argument, like `serve.emergency.EmergencyConfig`.

    The stability thresholds follow ScroogeVM's shape: a window is
    stable when its ``[spread_q_lo, spread_q_hi]`` percentile spread is
    at most ``spread_thresh`` *and* its sign-change rate is at most
    ``flip_thresh`` *and* its latest sample is at or below
    ``hot_util``. ``step_down`` should be several times ``step_up``
    (ratchet up, back off fast). The power-model fields convert CAPPING
    power samples back into utilization exactly like
    `serve.emergency.util_from_power`."""
    window: int = 16
    min_history: int = 4
    spread_q_lo: float = 0.1
    spread_q_hi: float = 0.9
    spread_thresh: float = 0.25
    flip_thresh: float = 0.6
    hot_util: float = 0.85
    ratchet_quorum: float = 0.9
    backoff_quorum: float = 0.5
    step_up: float = 0.05
    step_down: float = 0.25
    ratio_min: float = 1.0
    ratio_max: float = 2.0
    blades_per_chassis: int = 12
    p_dyn_per_core: float = ServerPowerModel().p_dyn_per_core
    idle_w_per_server: float = float(idle_power(F_MAX))
    #: when True, the pipelines clamp the *applied* ratio to
    #: ``ratio_min`` while the prediction scorecard reports
    #: ``model_stale`` (`obs.quality`) — the controller state keeps
    #: integrating, so the ratio resumes the moment the model is
    #: fresh again. Host-side gate; off by default to preserve the
    #: obs on/off bit-identity invariant.
    hold_on_stale: bool = False

    def __post_init__(self):
        if self.window < 2:
            raise ValueError(f"window must be >= 2, got {self.window}")
        if not 1 <= self.min_history <= self.window:
            raise ValueError(
                f"min_history must be in [1, window={self.window}], "
                f"got {self.min_history}")
        if not 0 <= self.spread_q_lo < self.spread_q_hi <= 1:
            raise ValueError(
                f"need 0 <= spread_q_lo < spread_q_hi <= 1, got "
                f"({self.spread_q_lo}, {self.spread_q_hi})")
        if not self.backoff_quorum <= self.ratchet_quorum:
            raise ValueError(
                f"backoff_quorum {self.backoff_quorum} must not exceed "
                f"ratchet_quorum {self.ratchet_quorum} (the hold band "
                "between them is what damps oscillation)")
        if not 0 < self.ratio_min <= self.ratio_max:
            raise ValueError(
                f"need 0 < ratio_min <= ratio_max, got "
                f"({self.ratio_min}, {self.ratio_max})")
        if self.step_up <= 0 or self.step_down <= 0:
            raise ValueError("step_up and step_down must be positive")

    @property
    def static_w(self) -> float:
        """Frequency-independent chassis floor (watts): every blade's
        idle draw — the intercept subtracted before a power sample is
        read back as utilization."""
        return self.blades_per_chassis * self.idle_w_per_server

    @classmethod
    def from_model(cls, model: ServerPowerModel | None = None,
                   **kw) -> "AdaptiveConfig":
        """Build a config calibrated to a `ServerPowerModel`."""
        model = model or ServerPowerModel()
        return cls(p_dyn_per_core=model.p_dyn_per_core, **kw)


class AdaptiveState(NamedTuple):
    """Controller state; all fixed-shape, batchable with leading dims
    (the sharded plane carries a leading shard axis). ``util`` is a
    per-chassis ring buffer — ``head`` is the next write slot and
    ``count`` saturates at the window length."""
    util: Any          # (..., C, W) — rolling utilization samples
    count: Any         # (..., C) i32 — valid samples, saturates at W
    head: Any          # (..., C) i32 — ring write position
    ratio: Any         # (...,) — current oversubscription ratio
    ratchets: Any      # (...,) i32 — cumulative up-steps taken
    backoffs: Any      # (...,) i32 — cumulative down-steps taken


class AdaptiveOutputs(NamedTuple):
    """Per-scan observables of one controller step."""
    ratio: Any         # (...,) — post-step ratio
    stable_frac: Any   # (...,) — stable / known chassis (0 if none)
    n_known: Any       # (...,) i32 — chassis with enough history
    n_stable: Any      # (...,) i32 — known chassis scored stable
    ratchet: Any       # (...,) bool — stepped up this scan
    backoff: Any       # (...,) bool — stepped down this scan
    hot: Any           # (...,) bool — some chassis over hot_util
    spread: Any        # (..., C) — percentile-spread score
    flip_rate: Any     # (..., C) — sign-change-rate score
    stable: Any        # (..., C) bool — per-chassis verdict


def init_adaptive(cfg: AdaptiveConfig, n_chassis: int, batch_shape=(),
                  xp=np, dtype=np.float32) -> AdaptiveState:
    """Fresh controller state at ratio 1.0 with empty windows — a
    controller that has seen nothing oversubscribes nothing."""
    shape_c = tuple(batch_shape) + (n_chassis,)
    return AdaptiveState(
        util=xp.zeros(shape_c + (cfg.window,), dtype),
        count=xp.zeros(shape_c, np.int32),
        head=xp.zeros(shape_c, np.int32),
        ratio=xp.ones(batch_shape, dtype),
        ratchets=xp.zeros(batch_shape, np.int32),
        backoffs=xp.zeros(batch_shape, np.int32))


def offered_power(cfg: AdaptiveConfig, rho_lv, util, xp=np):
    """Chassis draw implied by committed per-level ``p95*cores``
    aggregates at a utilization sample — the synthetic power feed the
    simulator pushes through the controller (the live pipeline gets
    real samples from the CAPPING stream instead):
    ``static + p_dyn * sum_l rho_l * util``."""
    rho = xp.sum(xp.asarray(rho_lv), axis=-1)
    return cfg.static_w + cfg.p_dyn_per_core * rho * xp.asarray(util)


def _util_from_power(cfg: AdaptiveConfig, rho_lv, power_w, xp):
    """Inverse of `offered_power` with the zero-commitment guard of
    `serve.emergency.util_from_power` (empty chassis read as idle)."""
    rho = xp.sum(rho_lv, axis=-1)
    dyn = xp.maximum(xp.asarray(power_w) - cfg.static_w, 0)
    return xp.where(rho > 0,
                    div(dyn, cfg.p_dyn_per_core
                        * xp.where(rho > 0, rho, 1), xp),
                    0.0)


def adaptive_step(cfg: AdaptiveConfig, st: AdaptiveState, rho_lv,
                  power_w, mask, xp=np):
    """One controller scan over a (batch of) chassis.

    rho_lv: (..., C, L) committed ``p95*cores`` per criticality level
    (`serve.emergency.chassis_rho_levels`) — converts the masked power
    samples back into utilization; power_w/mask: (..., C) — only
    ``mask`` rows carry a fresh sample (unmasked chassis keep their
    window and still participate in scoring with their old history).

    Returns ``(new_state, AdaptiveOutputs)``. Branchless and identical
    under numpy and jnp: cross-chassis reductions are integer sums and
    percentiles are sort + integer-index gathers (never interpolating
    ``percentile``), so the compiled twin is *bit-equal* to the numpy
    oracle — asserted on every scan by the sim backends."""
    rho_lv = xp.asarray(rho_lv)
    dtype = rho_lv.dtype
    W = cfg.window
    u_new = _util_from_power(cfg, rho_lv, power_w, xp).astype(dtype)

    # masked ring write: one-hot at head, then advance head/count
    slot = xp.arange(W, dtype=np.int32)
    write = mask[..., None] & (slot == st.head[..., None])
    util = xp.where(write, u_new[..., None], xp.asarray(st.util, dtype))
    count = xp.where(mask, xp.minimum(st.count + 1, W), st.count)
    head = xp.where(mask, (st.head + 1) % W, st.head)

    # chronological view (oldest -> newest); the valid samples are the
    # trailing `count` entries of the gather
    idx = (head[..., None] + slot) % W
    chrono = xp.take_along_axis(util, idx.astype(np.int32), axis=-1)
    valid = slot >= (W - count)[..., None]                # (..., C, W)

    # percentile spread: sort with invalid rows pushed to +inf, then
    # gather fixed integer indices (floor(q * (n-1)) — identical in
    # numpy and jnp, unlike interpolating percentile kernels)
    inf = dtype.type(np.inf)
    svals = xp.sort(xp.where(valid, chrono, inf), axis=-1)
    nm1 = xp.maximum(count - 1, 0).astype(dtype)
    i_lo = (dtype.type(cfg.spread_q_lo) * nm1).astype(np.int32)
    i_hi = (dtype.type(cfg.spread_q_hi) * nm1).astype(np.int32)
    q_lo = xp.take_along_axis(svals, i_lo[..., None], axis=-1)[..., 0]
    q_hi = xp.take_along_axis(svals, i_hi[..., None], axis=-1)[..., 0]
    zero = xp.zeros_like(q_lo)
    q_lo = xp.where(xp.isfinite(q_lo), q_lo, zero)
    q_hi = xp.where(xp.isfinite(q_hi), q_hi, zero)
    spread = q_hi - q_lo

    # sign-change rate over consecutive valid deltas (validity is a
    # suffix, so a pair is valid iff its left endpoint is)
    d = xp.where(valid[..., :-1], chrono[..., 1:] - chrono[..., :-1], 0)
    flips = xp.sum(
        ((xp.sign(d[..., 1:]) * xp.sign(d[..., :-1])) < 0).astype(
            np.int32), axis=-1)
    flip_rate = div(flips.astype(dtype),
                    xp.maximum(count - 2, 1).astype(dtype), xp)

    latest = chrono[..., -1]
    hot_c = (count >= 1) & (latest > dtype.type(cfg.hot_util))
    known = count >= cfg.min_history
    stable = known & (spread <= dtype.type(cfg.spread_thresh)) \
        & (flip_rate <= dtype.type(cfg.flip_thresh)) & ~hot_c

    # fleet decision: integer sums keep the reduction exact in f32
    n_known = xp.sum(known.astype(np.int32), axis=-1)
    n_stable = xp.sum(stable.astype(np.int32), axis=-1)
    hot = xp.sum(hot_c.astype(np.int32), axis=-1) > 0
    frac = div(n_stable.astype(dtype),
               xp.maximum(n_known, 1).astype(dtype), xp)
    ratchet = (n_known > 0) & ~hot \
        & (frac >= dtype.type(cfg.ratchet_quorum))
    backoff = hot | ((n_known > 0)
                     & (frac < dtype.type(cfg.backoff_quorum)))
    ratio = xp.clip(
        xp.asarray(st.ratio, dtype)
        + dtype.type(cfg.step_up) * ratchet.astype(dtype)
        - dtype.type(cfg.step_down) * backoff.astype(dtype),
        dtype.type(cfg.ratio_min), dtype.type(cfg.ratio_max))

    st2 = AdaptiveState(util=util, count=count, head=head, ratio=ratio,
                        ratchets=st.ratchets + ratchet.astype(np.int32),
                        backoffs=st.backoffs + backoff.astype(np.int32))
    return st2, AdaptiveOutputs(
        ratio=ratio, stable_frac=frac, n_known=n_known,
        n_stable=n_stable, ratchet=ratchet, backoff=backoff, hot=hot,
        spread=spread, flip_rate=flip_rate, stable=stable)


def retarget_pool(cfg: AdaptiveConfig, base_pool, ratio, committed,
                  xp=np):
    """Free-pool token level after the controller retargets the watt
    allowance: ``max(base_pool * ratio - committed, 0)``.

    ``base_pool`` is the ratio-1.0 rho-unit allowance
    (`serve.sharding.rho_pool_from_budget` of the *unscaled* budget,
    per shard), ``committed`` the rho already reserved by placed VMs.
    Minting (ratio grew) widens the free pool; retiring (ratio shrank)
    only drains it — the floor at zero is what keeps tokens committed
    to placed VMs irrevocable, so the conservation invariant
    ``committed + free == max(base*ratio, committed)`` holds through
    any mint/retire sequence."""
    base_pool = xp.asarray(base_pool)
    return xp.maximum(base_pool * ratio - xp.asarray(committed), 0)


def gate_ratio_on_stale(cfg: AdaptiveConfig, ratio, stale: bool,
                        xp=np):
    """Conservative-fallback gate on the *applied* oversubscription
    ratio: when the prediction scorecard reports ``stale`` (PSI drift
    or measured accuracy collapse — `obs.quality`), clamp the ratio
    to ``cfg.ratio_min``; otherwise pass it through unchanged.

    Pure and shape-generic (scalar or batched ratio). The controller
    state is never rewritten — staleness suppresses the aggressive
    ratio only while it lasts, and the integrated ratio resumes as
    soon as the model scores fresh again (the paper's "fall back to
    conservative when predictions can't be trusted" rule, made
    automatic)."""
    ratio = xp.asarray(ratio)
    if not stale:
        return ratio
    return xp.minimum(ratio, xp.asarray(cfg.ratio_min,
                                        dtype=ratio.dtype))


def decision_reason(before_ratio: float, out_ratio: float,
                    n_known: int, ratchet: bool, backoff: bool,
                    hot: bool) -> int:
    """Index into `REASON_NAMES` for one (scalar) controller decision —
    the host-side classification recorded into the audit ring."""
    if backoff:
        if out_ratio == before_ratio:
            return 6                       # backoff_floor
        return 4 if hot else 5             # backoff_hot / backoff_quorum
    if ratchet:
        return 3 if out_ratio == before_ratio else 2
    return 0 if n_known == 0 else 1        # hold_no_history / hold_band
