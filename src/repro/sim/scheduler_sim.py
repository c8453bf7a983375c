"""Cluster VM-scheduler simulation (paper §IV-E, Fig. 7).

Event-driven 30-day simulation of a 20-rack x 3-chassis x 12-blade
cluster. Like Azure's simulator, it runs the *actual* placement code
(`repro.core.placement`) for every arrival; our only extension is the
simulated prediction channel (the paper's only extension was simulating
calls to the ML system).

Reported metrics (paper's four):
  * deployment failure rate,
  * average empty-server ratio,
  * std-dev across chassis of the chassis score 1 - rho_peak/rho_max,
  * std-dev across servers of the server score .5(1+(gNUF-gUF)/N).

New: the placements the scheduler actually produced can be fed to the
batched fleet engine (`repro.sim.fleet`) to measure the *capping
dynamics* they induce — `evaluate_power_dynamics` vmaps the compiled
chassis simulator across the live chassis layouts, closing the loop
between Fig 7 (placement balance) and Figs 4-6 (per-VM capping).

`simulate(backend='serve')` routes every deployment group through the
online serving pipeline's batched placement scan
(`repro.serve.placement`) instead of the per-arrival numpy rule, so
Fig 7 metrics can be reproduced through the served path and checked
against the event-driven oracle (DESIGN.md §9).
"""
from __future__ import annotations

import contextlib
import heapq
import warnings
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

from repro.core.placement import ClusterState, SchedulerPolicy
from repro.core.resources import (N_RESOURCES, ResourceVector,
                                  trough_ratios)
from repro.sim import telemetry as tel
from repro.sim.fleet import (ServerSpec, VMSpec, build_layout,
                             build_uf_traces, run_fleet_layouts,
                             stack_layouts)

CORES_PER_BLADE = 40            # Table I: 2 x 20 cores
BLADES_PER_CHASSIS = 12
CHASSIS_PER_RACK = 3
RACKS = 20

#: Deterministic GB-per-vcore of every simulated VM. Memory demand is
#: a pure function of the core draw, so threading the GB ledger
#: through the sim consumes NO extra randomness — every rng stream,
#: and therefore every placement decision of the watt-only era, is
#: preserved bit for bit.
GB_PER_CORE = 4.0


@dataclass(frozen=True)
class PredictionChannel:
    """Simulated ML-system responses (Table III operating point).

    mode:
      'oracle'    — perfect workload type and P95 bucket;
      'ml'        — criticality flipped w.p. its measured error, P95
                    bucket resampled w.p. its measured error; low-
                    confidence queries fall back to conservative values
                    (UF, bucket 4), as the real scheduler does;
      'crit_only' — criticality as 'ml', utilization assumed 100 %
                    (Fig 7 orange bars);
      'none'      — no predictions (NoRule baseline ignores them).
    """
    mode: str = "ml"
    crit_recall_uf: float = 0.99     # P(pred UF | true UF)   — Table III
    crit_recall_nuf: float = 0.69    # P(pred NUF | true NUF)
    p95_accuracy: float = 0.84
    p95_high_conf: float = 0.73

    def predict(self, rng, true_uf: bool, true_p95: float):
        if self.mode == "oracle":
            return true_uf, true_p95
        if true_uf:
            uf = rng.random() < self.crit_recall_uf
        else:
            uf = not (rng.random() < self.crit_recall_nuf)
        if self.mode == "crit_only":
            return uf, 1.0
        if rng.random() > self.p95_high_conf:
            return uf, 1.0                       # low confidence -> 100 %
        if rng.random() < self.p95_accuracy:
            p95 = true_p95
        else:
            p95 = float(np.clip(true_p95 + rng.choice([-0.25, 0.25]),
                                0.125, 0.875))
        return uf, p95


@dataclass(frozen=True)
class PowerEvalSpec:
    """Post-run capping-dynamics evaluation (`evaluate_power_dynamics`
    over the placements the scheduler produced). ``budget_w`` is the
    per-chassis watt budget the fleet engine enforces."""
    budget_w: float
    chassis: int = 8
    duration_s: float = 60.0
    backend: str = "jax"

    def __post_init__(self):
        if not self.budget_w > 0:
            raise ValueError(
                f"PowerEvalSpec.budget_w must be > 0, got {self.budget_w}")


@dataclass(frozen=True)
class ServeBackendSpec:
    """Which placement path runs, and the resource budgets it admits
    against (DESIGN.md §16).

    backend:          'event' | 'serve' | 'serve-sharded' (see
                      `simulate`).
    admission_budget: per-chassis `ResourceVector` ceiling for the
                      serve path (None = unbounded; the legacy
                      ``admission_budget_w`` float is
                      ``ResourceVector(watts=w)``, decision-identical).
    cluster_budget:   global `ResourceVector` the sharded token pools
                      enforce (legacy ``cluster_budget_w`` likewise).
    shards:           state partitions of the sharded protocol.
    ingest_hosts:     per-host queues the arrival stream is dealt
                      over (sharded backend only).
    diurnal_ratchet:  condition the cores/GB admission ceilings (and
                      sharded pool axes) on the diurnal trough via
                      `core.resources.trough_ratios` — Coach-style
                      time-of-day oversubscription; the watts axis is
                      a breaker limit and never ratchets.
    """
    backend: str = "event"
    admission_budget: ResourceVector | None = None
    cluster_budget: ResourceVector | None = None
    shards: int = 1
    ingest_hosts: int = 1
    diurnal_ratchet: bool = False

    def __post_init__(self):
        if self.backend not in ("event", "serve", "serve-sharded"):
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.shards < 1:
            raise ValueError(f"shards must be >= 1, got {self.shards}")
        if self.ingest_hosts < 1:
            raise ValueError(f"ingest_hosts must be >= 1, "
                             f"got {self.ingest_hosts}")


@dataclass(frozen=True)
class SimSpec:
    """Everything `simulate` needs beyond the policy and the
    prediction channel — the one front door (DESIGN.md §16). Plane
    configs nest as typed sub-specs instead of a flat kwarg sprawl:
    ``serve`` picks the placement path and budgets, ``power`` the
    post-run capping evaluation, and ``emergency``/``adaptive``/
    ``ballooning`` the online planes (a `serve.emergency.
    EmergencyConfig`, `serve.adaptive.AdaptiveConfig` and
    `serve.ballooning.BallooningConfig` respectively — untyped here so
    the sim package never imports the serve package at module scope).
    """
    days: float = 30.0
    seed: int = 0
    deployments_per_hour: float = 8.0
    target_uf_core_ratio: float = 0.40
    sample_every_h: float = 2.0
    prefill_core_ratio: float = 0.0
    serve: ServeBackendSpec = field(default_factory=ServeBackendSpec)
    power: PowerEvalSpec | None = None
    emergency: object | None = None
    adaptive: object | None = None
    ballooning: object | None = None

    def __post_init__(self):
        if not self.days > 0:
            raise ValueError(f"days must be > 0, got {self.days}")
        if self.ballooning is not None and self.emergency is None:
            raise ValueError(
                "SimSpec.ballooning requires SimSpec.emergency — the "
                "balloon rung fires on the emergency plane's alarms")


_UNSET = object()

#: legacy `simulate` kwarg -> where it lives on `SimSpec` (doc string
#: for the DeprecationWarning; the adapter below does the mapping)
_LEGACY_SIM_KW = {
    "days": "days", "seed": "seed",
    "deployments_per_hour": "deployments_per_hour",
    "target_uf_core_ratio": "target_uf_core_ratio",
    "sample_every_h": "sample_every_h",
    "prefill_core_ratio": "prefill_core_ratio",
    "backend": "serve.backend",
    "admission_budget_w": "serve.admission_budget",
    "cluster_budget_w": "serve.cluster_budget",
    "serve_shards": "serve.shards",
    "n_ingest_hosts": "serve.ingest_hosts",
    "power_eval_budget_w": "power.budget_w",
    "power_eval_chassis": "power.chassis",
    "power_eval_duration_s": "power.duration_s",
    "power_eval_backend": "power.backend",
    "emergency_cfg": "emergency", "adaptive_cfg": "adaptive",
}


def _spec_from_legacy(spec: SimSpec | None, kw: dict) -> SimSpec:
    """Adapter: fold legacy `simulate` kwargs into a `SimSpec`,
    warning `DeprecationWarning` (tier-1 runs warnings-as-errors, so
    no in-repo caller may reach this path). Decision-identical by
    construction — every legacy value lands on the spec field the new
    body reads."""
    given = {k: v for k, v in kw.items() if v is not _UNSET}
    if not given:
        return spec if spec is not None else SimSpec()
    if spec is not None:
        raise TypeError("pass either spec=SimSpec(...) or legacy "
                        f"kwargs, not both: {sorted(given)}")
    warnings.warn(
        f"{', '.join(sorted(given))} as simulate() kwargs are "
        "deprecated; pass spec=SimSpec(...) (docs/resources.md has "
        "the migration table)", DeprecationWarning, stacklevel=3)
    top = {k: given.pop(k) for k in list(given)
           if "." not in _LEGACY_SIM_KW[k]
           and _LEGACY_SIM_KW[k] in ("days", "seed",
                                     "deployments_per_hour",
                                     "target_uf_core_ratio",
                                     "sample_every_h",
                                     "prefill_core_ratio")}
    serve_kw = {}
    for src, dst in (("backend", "backend"), ("serve_shards", "shards"),
                     ("n_ingest_hosts", "ingest_hosts")):
        if src in given:
            serve_kw[dst] = given.pop(src)
    for src, dst in (("admission_budget_w", "admission_budget"),
                     ("cluster_budget_w", "cluster_budget")):
        if src in given:
            w = given.pop(src)
            serve_kw[dst] = None if w is None \
                else ResourceVector(watts=float(w))
    power = None
    if given.get("power_eval_budget_w") is not None:
        power = PowerEvalSpec(
            budget_w=given.pop("power_eval_budget_w"),
            chassis=given.pop("power_eval_chassis", 8),
            duration_s=given.pop("power_eval_duration_s", 60.0),
            backend=given.pop("power_eval_backend", "jax"))
    else:
        for k in ("power_eval_budget_w", "power_eval_chassis",
                  "power_eval_duration_s", "power_eval_backend"):
            given.pop(k, None)
    top["emergency"] = given.pop("emergency_cfg", None)
    top["adaptive"] = given.pop("adaptive_cfg", None)
    assert not given, f"unmapped legacy kwargs: {sorted(given)}"
    return SimSpec(serve=ServeBackendSpec(**serve_kw), power=power,
                   **top)


@dataclass
class PowerEval:
    """Capping dynamics of scheduler-produced placements (fleet engine)."""
    chassis_ids: np.ndarray             # (B,) evaluated chassis
    uf_p95_latency: np.ndarray          # (B,)
    nuf_slowdown: np.ndarray            # (B,)
    rapl_engaged_frac: np.ndarray       # (B,)
    alert_frac: np.ndarray              # (B,)
    power_max_w: np.ndarray             # (B,)


@dataclass
class SimMetrics:
    failure_rate: float
    empty_server_ratio: float
    chassis_score_std: float
    server_score_std: float
    placements: int
    failures: int
    power: PowerEval | None = None
    #: power-emergency plane counters (`emergency_cfg` runs only):
    #: per-criticality throttled-seconds — the paper's Table-4-style
    #: impact axis (critical should stay near zero under
    #: criticality-aware apportionment) — plus alarm and migration
    #: counts. `throttled_s` is (L,) in the emergency plane's level
    #: order (index `serve.emergency.CRIT_NUF` = 0, `CRIT_UF` = 1 —
    #: the `obs.LEVEL_NAMES` order), matching `EmergencyState.
    #: throttled_s` instead of the historical pair of drifting scalar
    #: names; those survive as read-only properties.
    throttled_s: np.ndarray = field(default_factory=lambda: np.zeros(2))
    alarms: int = 0
    migrations: int = 0
    #: ballooning rung (`SimSpec.ballooning` runs only): inflation
    #: events, total GB reclaimed across the run, and the GB still
    #: ballooned out at the end — all 0 when the rung is off
    balloon_events: int = 0
    balloon_reclaimed_gb: float = 0.0
    ballooned_gb: float = 0.0
    #: adaptive-ratio controller (`adaptive_cfg` runs only): the final
    #: oversubscription ratio and the up/down step counts — 1.0/0/0
    #: when the controller is off
    adaptive_ratio: float = 1.0
    adaptive_ratchets: int = 0
    adaptive_backoffs: int = 0
    #: measured predicted-vs-realized labels (DESIGN.md §17): every
    #: `PredictionChannel.predict` call is scored against the ground
    #: truth it was sampled from — ``crit_confusion[true, pred]``
    #: (2, 2) over criticality, ``p95_confusion[true, pred]`` (4, 4)
    #: over P95 buckets. Accuracy is an *output* of the run, not the
    #: channel's generative constant (`measured_p95_accuracy` vs the
    #: assumed ``p95_accuracy`` knob).
    crit_confusion: np.ndarray = field(
        default_factory=lambda: np.zeros((2, 2), np.int64))
    p95_confusion: np.ndarray = field(
        default_factory=lambda: np.zeros((4, 4), np.int64))

    @property
    def measured_crit_accuracy(self) -> float:
        """Realized criticality-prediction accuracy over the run
        (NaN when nothing was scored)."""
        n = self.crit_confusion.sum()
        return float(np.trace(self.crit_confusion) / n) if n \
            else float("nan")

    @property
    def measured_p95_accuracy(self) -> float:
        """Realized P95-bucket-prediction accuracy over the run
        (NaN when nothing was scored)."""
        n = self.p95_confusion.sum()
        return float(np.trace(self.p95_confusion) / n) if n \
            else float("nan")

    @property
    def nuf_throttled_s(self) -> float:
        """Non-critical throttled-seconds (``throttled_s[CRIT_NUF]``)."""
        return float(self.throttled_s[0])

    @property
    def uf_throttled_s(self) -> float:
        """Critical throttled-seconds (``throttled_s[CRIT_UF]``)."""
        return float(self.throttled_s[1])


class _EmergencySim:
    """Power-emergency plane driven inside `simulate` (DESIGN.md §12).

    Holds one fleet-wide `serve.emergency.EmergencyState` (f64) and
    steps it at every deployment event: the committed per-criticality
    aggregates are scaled by the deterministic diurnal utilization
    sample (`sim.telemetry.diurnal_util`) into per-chassis power
    samples, the alarm + apportionment kernel consumes them, and
    chassis whose critical level dwells capped past the threshold get
    a migration plan (`serve.mitigation`) applied to the cluster
    state as paired depart/arrive moves.

    The numpy execution is the oracle; with `use_jax` (the serve
    backends) every scan ALSO runs the compiled jnp kernel in x64 and
    asserts it bit-identical — the acceptance invariant, checked on
    every scan rather than trusted to a test fixture. The sample set
    is a pure function of simulation time, so the emergency trace is
    identical for every backend and ingest-host count."""

    def __init__(self, cfg, n_chassis: int, chassis_of: np.ndarray,
                 use_jax: bool, bcfg=None):
        from repro.serve import ballooning, emergency, mitigation
        self.emg, self.mit = emergency, mitigation
        self.bal = ballooning
        self.cfg = cfg
        self.bcfg = bcfg
        self.n_chassis = n_chassis
        self.chassis_of = chassis_of
        self.use_jax = use_jax
        self.st = emergency.init_emergency(n_chassis, xp=np,
                                           dtype=np.float64)
        self.bst = None if bcfg is None else \
            ballooning.init_ballooning(n_chassis, xp=np,
                                       dtype=np.float64)
        self.alarms = 0
        self.migrations = 0
        self.balloon_events = 0
        self.balloon_reclaimed_gb = 0.0
        # span factory for the observability plane; `simulate` rebinds
        # it to `Observability.span` when tracing is on
        self.span = lambda name: contextlib.nullcontext()

    def _rho_lv(self, state) -> np.ndarray:
        c = self.n_chassis
        return np.stack(
            [np.bincount(self.chassis_of, weights=state.gamma_nuf,
                         minlength=c),
             np.bincount(self.chassis_of, weights=state.gamma_uf,
                         minlength=c)], axis=-1)

    def scan(self, t_h: float, state, vm_live: dict,
             mem_nuf: np.ndarray = None, mem_chassis: np.ndarray = None,
             gb_cap: np.ndarray = None) -> None:
        """One emergency scan at simulation time `t_h` (hours).

        `mem_nuf`/`mem_chassis`: (C,) committed GB (NUF slice and
        total) — the ballooning rung's headroom and the migration
        planner's GB-fit ledger; `gb_cap`: (C,) chassis GB capacity
        (None disables the destination GB-fit check)."""
        emg = self.emg
        u = float(tel.diurnal_util(t_h))
        rho_lv = self._rho_lv(state)
        idx = np.arange(self.n_chassis)
        stamps = t_h * 3600.0 + (idx + 1) * 1e-7
        power = np.asarray(emg.sampled_power(
            self.cfg, rho_lv, u, np.zeros((self.n_chassis, 2), np.int32),
            np.zeros(self.n_chassis, bool), np))
        pw, mask, ts = emg.scatter_samples(self.n_chassis, idx, power,
                                           stamps, np, np.float64)
        # ballooning rung: absorb the watt deficit the NUF frequency
        # floor cannot, by powering NUF DRAM down — BEFORE the capping
        # step consumes the sample, so a fully served demand never
        # touches the critical level at all
        bst2 = bout = None
        pw_step = pw
        if self.bst is not None:
            nuf = np.zeros(self.n_chassis) if mem_nuf is None else mem_nuf
            bst2, bout = self.bal.balloon_step(
                self.bcfg, self.cfg, self.bst, rho_lv, pw, nuf, mask, np)
            pw_step = bout.power_adj_w
        st2, out = emg.masked_step(self.cfg, self.st, rho_lv, pw_step,
                                   mask, ts, np)
        if self.use_jax:
            import jax
            import jax.numpy as jnp
            with jax.enable_x64(True):
                pwj = jnp.asarray(pw)
                if self.bst is not None:
                    bstj, boutj = self.bal.balloon_step(
                        self.bcfg, self.cfg,
                        jax.tree.map(jnp.asarray, self.bst),
                        jnp.asarray(rho_lv), pwj, jnp.asarray(nuf),
                        jnp.asarray(mask), jnp)
                    assert np.array_equal(np.asarray(bstj.ballooned_gb),
                                          bst2.ballooned_gb), \
                        "ballooning kernel diverged from numpy oracle"
                    pwj = boutj.power_adj_w
                stj, outj = emg.masked_step(
                    self.cfg, jax.tree.map(jnp.asarray, self.st),
                    jnp.asarray(rho_lv), pwj,
                    jnp.asarray(mask), jnp.asarray(ts), jnp)
            for a, b in zip(st2, stj):
                assert np.array_equal(np.asarray(a), np.asarray(b)), \
                    "serve emergency kernel diverged from numpy oracle"
        self.st = st2
        if bst2 is not None:
            self.bst = bst2
            self.balloon_events += int(np.asarray(bout.inflated).sum())
            self.balloon_reclaimed_gb += float(
                np.asarray(bout.reclaimed_gb).sum())
        self.alarms += int(out.alarm.sum())
        # no chassis past the alarm window may exceed its budget when
        # the cut was achievable within the floors (the RAPL-leftover
        # rows are physically pinned at the all-core frequency floor)
        achievable = out.alarm & (out.leftover_w <= 1e-6)
        assert (np.asarray(out.power_after_w)[achievable]
                <= self.cfg.chassis_budget_w + 1e-6).all(), \
            "chassis exceeded its budget past the alarm window"
        self._mitigate(u, state, vm_live, mem_chassis, gb_cap)

    def _mitigate(self, u: float, state, vm_live: dict,
                  mem_chassis: np.ndarray = None,
                  gb_cap: np.ndarray = None) -> None:
        emg, mit = self.emg, self.mit
        due = np.asarray(emg.mitigation_due(self.cfg, self.st, np))
        if not due.any() or not vm_live:
            return
        tokens = np.fromiter(vm_live.keys(), np.int64, len(vm_live))
        tokens.sort()                       # deterministic registry order
        rows = [vm_live[int(k)] for k in tokens]
        live = mit.LiveVMs(
            server=np.array([r[0] for r in rows], np.int32),
            cores=np.array([r[1] for r in rows], np.float64),
            p95_eff=np.array([r[2] for r in rows], np.float64),
            is_uf=np.array([r[3] for r in rows], bool),
            token=tokens,
            mem_gb=np.array([r[4] for r in rows], np.float64))
        with self.span("migrate"):
            plan = mit.plan_migrations(
                self.cfg, live, self.chassis_of, state.free_cores,
                self._rho_lv(state), u, due,
                mem_chassis=mem_chassis, gb_cap=gb_cap)
            # paired depart/arrive application; pairs touch disjoint
            # VMs, so plan order == any merged event order (the
            # pipeline path routes the same pairs through the ingest
            # merge)
            for m in range(len(plan)):
                cores = float(plan.cores[m])
                p95, uf = float(plan.p95_eff[m]), bool(plan.is_uf[m])
                mem = float(plan.mem_gb[m])
                src, dst = int(plan.src_server[m]), int(plan.dst_server[m])
                state.remove(src, cores, p95, uf)
                state.place(dst, cores, p95, uf)
                if mem_chassis is not None:
                    mem_chassis[self.chassis_of[src]] -= mem
                    mem_chassis[self.chassis_of[dst]] += mem
                vm_live[int(plan.token[m])] = (dst, cores, p95, uf, mem)
        self.migrations += len(plan)
        self.st = emg.reset_dwell(self.st, due, np)


class _AdaptiveSim:
    """Adaptive-ratio controller driven inside `simulate`
    (DESIGN.md §15, docs/adaptive.md).

    Holds one fleet-wide `serve.adaptive.AdaptiveState` (f64) and
    steps it at every deployment event from the same synthetic power
    samples the emergency plane reads: the committed per-criticality
    aggregates scaled by the deterministic diurnal utilization sample
    (`sim.telemetry.diurnal_util`) through `serve.adaptive.
    offered_power`. The resulting ratio scales the serve path's
    admission ceiling (and, sharded, the global token allowance)
    before the *next* placement scan — closed loop, one scan behind,
    exactly like the pipeline's eager cap-window stepping.

    The numpy execution is the oracle; with `use_jax` every scan ALSO
    runs the compiled jnp twin in x64 and asserts it bit-identical —
    the same acceptance invariant `_EmergencySim` enforces."""

    def __init__(self, cfg, n_chassis: int, chassis_of: np.ndarray,
                 use_jax: bool):
        from repro.serve import adaptive
        self.adp = adaptive
        self.cfg = cfg
        self.n_chassis = n_chassis
        self.chassis_of = chassis_of
        self.use_jax = use_jax
        self.st = adaptive.init_adaptive(cfg, n_chassis, xp=np,
                                         dtype=np.float64)
        self.span = lambda name: contextlib.nullcontext()

    def _rho_lv(self, state) -> np.ndarray:
        c = self.n_chassis
        return np.stack(
            [np.bincount(self.chassis_of, weights=state.gamma_nuf,
                         minlength=c),
             np.bincount(self.chassis_of, weights=state.gamma_uf,
                         minlength=c)], axis=-1)

    @property
    def ratio(self) -> float:
        """Current fleet oversubscription ratio (starts at 1.0)."""
        return float(self.st.ratio)

    @property
    def ratchets(self) -> int:
        """Up-steps taken so far."""
        return int(self.st.ratchets)

    @property
    def backoffs(self) -> int:
        """Down-steps taken so far."""
        return int(self.st.backoffs)

    def scan(self, t_h: float, state) -> None:
        """One controller scan at simulation time `t_h` (hours)."""
        adp = self.adp
        u = float(tel.diurnal_util(t_h))
        rho_lv = self._rho_lv(state)
        power = np.asarray(adp.offered_power(self.cfg, rho_lv, u, np))
        mask = np.ones(self.n_chassis, bool)
        st2, out = adp.adaptive_step(self.cfg, self.st, rho_lv, power,
                                     mask, np)
        if self.use_jax:
            import jax
            import jax.numpy as jnp
            with jax.enable_x64(True):
                stj, _ = adp.adaptive_step(
                    self.cfg, jax.tree.map(jnp.asarray, self.st),
                    jnp.asarray(rho_lv), jnp.asarray(power),
                    jnp.asarray(mask), jnp)
            for a, b in zip(st2, stj):
                assert np.array_equal(np.asarray(a), np.asarray(b)), \
                    "adaptive controller kernel diverged from numpy " \
                    "oracle"
        self.st = st2


def evaluate_power_dynamics(vm_live: dict, chassis_of: np.ndarray,
                            n_chassis: int, budget_w: float,
                            blades_per_chassis: int = BLADES_PER_CHASSIS,
                            cores_per_blade: int = CORES_PER_BLADE,
                            sample_chassis: int = 8,
                            duration_s: float = 60.0, seed: int = 0,
                            backend: str = "jax") -> PowerEval:
    """Run the fleet engine on the placements the scheduler produced.

    Picks the `sample_chassis` most-allocated chassis, packs each one's
    live VMs into padded fleet layouts (UF VMs' offered load = their
    effective P95), and simulates the per-VM capping stack on all of
    them in one vmapped call. Different chassis have different VM
    placements — the layout arrays are the batch axis.
    """
    per_server = defaultdict(list)
    alloc = np.zeros(n_chassis)
    for (srv, cores, p95e, ufp, *_mem) in vm_live.values():
        per_server[srv].append(VMSpec(int(cores), bool(ufp),
                                      load=float(p95e)))
        alloc[chassis_of[srv]] += cores
    picked = np.argsort(-alloc)[:sample_chassis]
    picked = picked[alloc[picked] > 0]

    def chassis_specs(c):
        servers = np.nonzero(chassis_of == c)[0]
        return [ServerSpec(vms=per_server.get(int(s), []),
                           n_cores=cores_per_blade) for s in servers]

    all_specs = [chassis_specs(c) for c in picked]
    pad_uf = max(1, max(sum(v.is_uf for s in sp for v in s.vms)
                        for sp in all_specs))
    pad_nuf = max(1, max(sum(not v.is_uf for s in sp for v in s.vms)
                         for sp in all_specs))
    layouts = [build_layout(sp, pad_uf_to=pad_uf, pad_nuf_to=pad_nuf,
                            pad_cores_to=cores_per_blade)
               for sp in all_specs]
    n_steps = int(duration_s / 0.2)
    traces = np.stack([build_uf_traces(lo, n_steps, seed + i)
                       for i, lo in enumerate(layouts)])
    la = stack_layouts(layouts)
    res = run_fleet_layouts(
        la, np.stack([lo.uf_valid for lo in layouts]),
        np.stack([lo.nuf_valid for lo in layouts]),
        np.stack([lo.nuf_cores for lo in layouts]),
        np.full(len(layouts), budget_w), "per_vm", traces,
        backend=backend)
    return PowerEval(chassis_ids=picked,
                     uf_p95_latency=res.uf_p95_latency,
                     nuf_slowdown=res.nuf_slowdown,
                     rapl_engaged_frac=res.rapl_engaged_frac,
                     alert_frac=res.alert_frac,
                     power_max_w=res.power_w.max(-1))


def _sample_vm(rng):
    cores = int(rng.choice(tel.CORE_SIZES, p=tel.CORE_PROBS))
    life_h = tel._sample_bucket(rng, tel.LIFETIME_BUCKETS,
                                tel.LIFETIME_PROBS)
    return cores, life_h


def _sample_deployment_size(rng):
    return int(tel._sample_bucket(rng, tel.DEPLOY_SIZE_BUCKETS,
                                  tel.DEPLOY_SIZE_PROBS))


#: Serve-backend micro-batch pad (max deployment size is 60 — Table I).
SERVE_GROUP_PAD = 64


def simulate(policy: SchedulerPolicy, channel: PredictionChannel,
             spec: SimSpec | None = None, *,
             trace: list | None = None,
             obs=None,
             days=_UNSET, seed=_UNSET,
             deployments_per_hour=_UNSET,
             target_uf_core_ratio=_UNSET,
             sample_every_h=_UNSET,
             power_eval_budget_w=_UNSET,
             power_eval_chassis=_UNSET,
             power_eval_duration_s=_UNSET,
             power_eval_backend=_UNSET,
             backend=_UNSET,
             admission_budget_w=_UNSET,
             serve_shards=_UNSET,
             n_ingest_hosts=_UNSET,
             cluster_budget_w=_UNSET,
             emergency_cfg=_UNSET,
             adaptive_cfg=_UNSET,
             prefill_core_ratio=_UNSET) -> SimMetrics:
    """Run the 30-day simulation. Table I parameters throughout:
    UF:NUF core ratio 4:6, UF P95 ~ 65 % (bucket 3), NUF ~ 44 %
    (bucket 2).

    ``spec``, a `SimSpec`, is the front door: every run parameter
    lives on it (``SimSpec(serve=ServeBackendSpec(...),
    power=PowerEvalSpec(...), emergency=..., adaptive=...,
    ballooning=...)``). The flat keyword arguments of the scalar-watt
    era are still accepted — mapped onto the same spec fields by a
    thin adapter, decision-identically — but warn
    `DeprecationWarning`; ``trace`` and ``obs`` are live attachments,
    not run parameters, and stay real keywords. Every VM carries
    ``GB_PER_CORE`` GB per vcore (deterministic, so the rng streams —
    and every scalar-era decision — are untouched); the committed GB
    ledger feeds the serve path's per-resource admission, the
    ballooning rung's headroom, and the migration planner's
    destination fit.

    serve.backend:
      'event' — the per-arrival numpy path (`SchedulerPolicy.choose`),
                the decision oracle;
      'serve' — each deployment group is placed by one call to the
                serving pipeline's batched scorer
                (`repro.serve.placement.place_batch`, padded to
                SERVE_GROUP_PAD), exercising the online path against
                the same arrival stream. `serve.admission_budget`
                adds the serve path's per-chassis (watts, cores, GB)
                admission ceilings (rejections count as failures; a
                watt-only vector reproduces the scalar-era decisions
                bit for bit). Every serve-sharded scan additionally
                asserts per-resource token conservation: the pool
                delta each finite axis reports must equal the summed
                demand of the VMs it admitted;
      'serve-sharded' —
                each group runs the sharded consistent-placement
                protocol (`repro.serve.sharding`, docs/sharding.md)
                over `serve.shards` state partitions. With 1 shard it
                is decision-identical to 'serve' (asserted in tests);
                with N it bounds the objective regret of concurrent
                placement while never exceeding `serve.cluster_budget`
                (the global per-resource budget the per-shard token
                pools enforce — tracked net of departures across the
                run). Arrivals reach the protocol through the
                cross-host ingest merge (`repro.serve.ingest`,
                docs/ingest.md): the group is dealt round-robin over
                `serve.ingest_hosts` per-host queues with strictly
                increasing stamps and timestamp-merged back, so the
                merged order — and every placement decision — is
                identical for any host count (1 host == today's
                single-queue path, asserted in tests).
    `prefill_core_ratio` warm-starts the cluster before the event loop:
    VMs are sampled and placed by the event-path rule (identically for
    every backend — the stream draws from the same rng prefix) until
    that fraction of the fleet's cores is committed, with normal
    lifetimes feeding the departure heap. Short runs can then exercise
    occupancy regimes — like a 2x-oversubscribed fleet near its alarm
    threshold — that an empty 720-server cluster would need weeks of
    simulated arrivals to reach.

    `spec.emergency`, a `serve.emergency.EmergencyConfig`, turns on
    the online power-emergency plane (DESIGN.md §12,
    docs/emergency.md): every deployment event also scans all chassis
    — committed aggregates scaled by the deterministic diurnal
    utilization sample (`sim.telemetry.diurnal_util`) become power
    samples, alarms apportion cuts lowest-criticality-first,
    per-criticality throttled-seconds accrue into the metrics, and
    chassis whose critical level stays capped past the dwell
    threshold get their cheapest critical VMs migrated to headroom
    chassis (`serve.mitigation` — GB-fit-checked when the admission
    budget carries a GB axis). The scan asserts that no alarmed
    chassis with an achievable cut exceeds its budget, and under the
    serve backends additionally asserts the compiled jnp kernel
    bit-identical to the numpy oracle on every scan.

    `spec.ballooning`, a `serve.ballooning.BallooningConfig`, arms
    the middle mitigation rung (cap -> balloon -> migrate; DESIGN.md
    §16, docs/resources.md): on every alarmed scan the watt deficit
    the NUF frequency floor cannot absorb is served by ballooning NUF
    memory out (`serve.ballooning.balloon_step`, the committed-GB
    ledger bounding the reclaim) *before* the capping step consumes
    the sample — fewer critical throttled-seconds and fewer
    migrations at the same watt budget, counted into the metrics.
    Requires `spec.emergency`; the jnp twin is asserted bit-identical
    on every scan like the other planes.

    `spec.adaptive`, a `serve.adaptive.AdaptiveConfig`, turns on the
    closed-loop adaptive oversubscription controller (DESIGN.md §15,
    docs/adaptive.md) and requires a serve backend — it modulates the
    serve path's admission ceiling, which the event oracle does not
    read. Every deployment event also steps the controller from the
    same diurnal power samples; the resulting ratio scales the
    admission budget's per-chassis watt ceiling (and, sharded, the
    cluster watt allowance, never revoking committed tokens) for the
    *next* placement scan. Under the serve backends every controller
    scan asserts the compiled jnp twin bit-identical to the numpy
    oracle, like the emergency plane.

    `trace`, if given, collects the chosen server (or failure code)
    per placement attempt — the decision-equivalence probe.

    `obs`, a `repro.obs.Observability`, turns on the fleet
    observability plane (DESIGN.md §14): placement and emergency
    stages run under spans, the sharded backend counts its compiled
    round dispatches into the registry, and the final `SimMetrics`
    is exported through `repro.obs.record_sim_metrics` so sim runs
    snapshot under the same schema as live serve runs. Decisions are
    bit-identical with `obs` on or off (asserted in tests)."""
    spec = _spec_from_legacy(spec, dict(
        days=days, seed=seed,
        deployments_per_hour=deployments_per_hour,
        target_uf_core_ratio=target_uf_core_ratio,
        sample_every_h=sample_every_h,
        prefill_core_ratio=prefill_core_ratio,
        power_eval_budget_w=power_eval_budget_w,
        power_eval_chassis=power_eval_chassis,
        power_eval_duration_s=power_eval_duration_s,
        power_eval_backend=power_eval_backend,
        backend=backend, admission_budget_w=admission_budget_w,
        serve_shards=serve_shards, n_ingest_hosts=n_ingest_hosts,
        cluster_budget_w=cluster_budget_w,
        emergency_cfg=emergency_cfg, adaptive_cfg=adaptive_cfg))
    sv = spec.serve
    backend_name = sv.backend
    if spec.adaptive is not None and backend_name == "event":
        # the controller modulates the serve admission ceiling; the
        # event oracle has no such ceiling, so silently accepting the
        # knob would report a ratio that never bound anything
        raise ValueError("SimSpec.adaptive requires a serve backend")
    if sv.ingest_hosts != 1 and backend_name != "serve-sharded":
        # only the sharded backend routes groups through the ingest
        # merge; silently ignoring the knob would make an invariance
        # assertion on another backend a vacuous pass
        raise ValueError(
            f"ingest_hosts={sv.ingest_hosts} requires "
            f"backend='serve-sharded', got {backend_name!r}")
    if sv.diurnal_ratchet and backend_name == "event":
        raise ValueError(
            "diurnal_ratchet conditions the serve admission ceilings; "
            "it requires a serve backend")
    if backend_name in ("serve", "serve-sharded"):
        import jax
        import jax.numpy as jnp
        from repro.serve.admission import resource_caps_from_budget
        from repro.serve.ingest import kway_merge
        from repro.serve.placement import device_state, place_batch
        from repro.serve.sharding import (place_group_sharded,
                                          resource_pool_from_budget,
                                          shard_state)
    span = obs.span if obs is not None else \
        (lambda name: contextlib.nullcontext())
    rng = np.random.default_rng(spec.seed)
    n_servers = RACKS * CHASSIS_PER_RACK * BLADES_PER_CHASSIS
    chassis_of = np.arange(n_servers) // BLADES_PER_CHASSIS
    state = ClusterState(
        n_servers=n_servers, cores_per_server=CORES_PER_BLADE,
        chassis_of_server=chassis_of,
        n_chassis=n_servers // BLADES_PER_CHASSIS)
    # committed-GB ledgers (total and NUF slice per chassis) — the
    # joint admission / ballooning / migration planes' memory view
    mem_chassis = np.zeros(state.n_chassis)
    mem_nuf_chassis = np.zeros(state.n_chassis)

    if backend_name in ("serve", "serve-sharded"):
        serve_res_cap = resource_caps_from_budget(
            sv.admission_budget or ResourceVector(),
            BLADES_PER_CHASSIS, state.n_chassis)
        serve_pool_total = resource_pool_from_budget(
            sv.cluster_budget or ResourceVector(), n_servers)
        pool_finite = np.isfinite(serve_pool_total)
        gb_cap_col = serve_res_cap[:, 2].astype(np.float64)
        gb_cap = gb_cap_col if np.isfinite(gb_cap_col).any() else None
    else:
        gb_cap = None
    emer = None
    if spec.emergency is not None:
        emer = _EmergencySim(spec.emergency, state.n_chassis,
                             chassis_of,
                             use_jax=backend_name != "event",
                             bcfg=spec.ballooning)
        if obs is not None:
            emer.span = obs.span
    adp = None
    if spec.adaptive is not None:
        adp = _AdaptiveSim(spec.adaptive, state.n_chassis, chassis_of,
                           use_jax=True)
        if obs is not None:
            adp.span = obs.span
    departures: list = []        # heap of (time, vm_token)
    # token -> (server, cores, p95eff, uf_pred, mem_gb)
    vm_live: dict = {}
    token = 0
    placements = failures = 0
    # measured predicted-vs-realized scoring (DESIGN.md §17): every
    # channel.predict is scored against the ground truth it was
    # sampled from — consumes no randomness and feeds nothing back
    # into placement, so the decision stream is untouched
    from repro.core.features import p95_bucket as _p95_bucket
    crit_cm = np.zeros((2, 2), np.int64)
    p95_cm = np.zeros((4, 4), np.int64)
    quality = None if obs is None else obs.quality

    def _score(true_uf, true_p95, uf_pred, p95_pred):
        tb = int(_p95_bucket(true_p95 * 100.0))
        pb = int(_p95_bucket(p95_pred * 100.0))
        crit_cm[int(true_uf), int(uf_pred)] += 1
        p95_cm[tb, pb] += 1
        if quality is not None:
            quality.record(int(true_uf), tb, int(uf_pred), pb)
    # warm start (identical for every backend: one rng prefix, the
    # event-path placement rule). A snapshot of a running fleet is
    # length-biased — long-lived VMs dominate the standing population —
    # so prefill lifetimes sample the duration-weighted buckets with a
    # uniform residual, keeping the occupancy roughly stationary
    # instead of draining at the short-life rate.
    target_cores = spec.prefill_core_ratio * n_servers * CORES_PER_BLADE
    mids = np.array([(lo + hi) / 2 for lo, hi in tel.LIFETIME_BUCKETS])
    standing_probs = tel.LIFETIME_PROBS * mids
    standing_probs = standing_probs / standing_probs.sum()
    filled = 0.0
    while filled < target_cores:
        cores = int(rng.choice(tel.CORE_SIZES, p=tel.CORE_PROBS))
        life_h = rng.random() * tel._sample_bucket(
            rng, tel.LIFETIME_BUCKETS, standing_probs)
        true_uf = rng.random() < spec.target_uf_core_ratio
        true_p95 = float(np.clip(
            rng.normal(0.65 if true_uf else 0.44, 0.12), 0.05, 1.0))
        uf_pred, p95_pred = channel.predict(rng, true_uf, true_p95)
        _score(true_uf, true_p95, uf_pred, p95_pred)
        p95_eff = policy.effective_p95(p95_pred)
        srv = policy.choose(state, cores, uf_pred)
        if srv is None:
            break
        mem = cores * GB_PER_CORE
        state.place(srv, cores, p95_eff, uf_pred)
        mem_chassis[chassis_of[srv]] += mem
        if not uf_pred:
            mem_nuf_chassis[chassis_of[srv]] += mem
        vm_live[token] = (srv, cores, p95_eff, uf_pred, mem)
        heapq.heappush(departures, (life_h, token))
        token += 1
        filled += cores
    t = 0.0
    next_sample = 0.0
    empty_samples, chassis_stds, server_stds = [], [], []
    horizon = spec.days * 24.0

    while t < horizon:
        t += rng.exponential(1.0 / spec.deployments_per_hour)
        # departures first
        while departures and departures[0][0] <= t:
            _, tok = heapq.heappop(departures)
            srv, cores, p95e, ufp, mem = vm_live.pop(tok)
            state.remove(srv, cores, p95e, ufp)
            mem_chassis[chassis_of[srv]] -= mem
            if not ufp:
                mem_nuf_chassis[chassis_of[srv]] -= mem
        while next_sample <= t and next_sample < horizon:
            busy = state.free_cores < CORES_PER_BLADE
            empty_samples.append(1.0 - busy.mean())
            chassis_stds.append(float(np.std(state.score_chassis())))
            server_stds.append(float(np.std(state.score_server(True))))
            next_sample += spec.sample_every_h
        if t >= horizon:
            break
        if emer is not None:
            # windowed/SLO feeds (DESIGN.md §17) read plane state
            # before/after the scan and hand the *deltas* to the
            # watermark-clock pillars — never the emergency_* registry
            # counters, which the end-of-run `record_sim_metrics`
            # export owns
            feeds = obs is not None and (obs.windows is not None
                                         or obs.slo is not None)
            if feeds:
                pre_alarms = emer.alarms
                pre_thr = np.asarray(
                    emer.emg.throttled_by_level(emer.st), np.float64)
            with span("emergency"):
                emer.scan(t, state, vm_live, mem_nuf=mem_nuf_chassis,
                          mem_chassis=mem_chassis, gb_cap=gb_cap)
            if feeds:
                t_s = t * 3600.0
                d_alarms = emer.alarms - pre_alarms
                d_thr = np.asarray(
                    emer.emg.throttled_by_level(emer.st),
                    np.float64) - pre_thr
                if obs.windows is not None:
                    if d_alarms:
                        obs.windows.observe(t_s, "alarms",
                                            n=int(d_alarms))
                    if d_thr[1] > 0:
                        obs.windows.observe(t_s, "uf_throttled_s",
                                            float(d_thr[1]))
                    obs.windows.advance(t_s)
                if obs.slo is not None:
                    obs.slo.ingest(t_s, "emergency_alarms_total",
                                   float(d_alarms))
                    for lvl, d in zip(("nuf", "uf"), d_thr):
                        obs.slo.ingest(
                            t_s, "emergency_throttled_seconds_total",
                            float(d), level=lvl)
                    obs.slo.evaluate(t_s)
        if adp is not None:
            with span("adaptive"):
                adp.scan(t, state)
        # sample the whole deployment group first (placement consumes
        # no randomness, so both backends see the same stream), then
        # place per-VM (event) or via one batched scan (serve)
        group = []
        for _ in range(_sample_deployment_size(rng)):
            cores, life_h = _sample_vm(rng)
            true_uf = rng.random() < spec.target_uf_core_ratio
            true_p95 = float(np.clip(
                rng.normal(0.65 if true_uf else 0.44, 0.12), 0.05, 1.0))
            uf_pred, p95_pred = channel.predict(rng, true_uf, true_p95)
            _score(true_uf, true_p95, uf_pred, p95_pred)
            group.append((cores, life_h, uf_pred,
                          policy.effective_p95(p95_pred)))
        if backend_name in ("serve", "serve-sharded"):
            n = len(group)
            assert n <= SERVE_GROUP_PAD, \
                "deployment group exceeds SERVE_GROUP_PAD"
            if backend_name == "serve-sharded":
                # cross-host ingest: deal the group round-robin over
                # per-host queues with strictly increasing stamps and
                # timestamp-merge it back (the serve.ingest merge).
                # Unique stamps make the merged order the arrival
                # order for ANY host count — 1 host is exactly the
                # single-queue path, asserted in tests.
                host_of = np.arange(n) % sv.ingest_hosts
                stamps = t + np.arange(1, n + 1) * 1e-7
                rows = [np.flatnonzero(host_of == h)
                        for h in range(sv.ingest_hosts)]
                mh, mi = kway_merge([stamps[r] for r in rows])
                order = np.array([rows[h][i]
                                  for h, i in zip(mh, mi)], np.int64)
            else:
                order = np.arange(n, dtype=np.int64)
            pad = np.zeros(SERVE_GROUP_PAD, np.float64)
            cores_a, uf_a, p95_a = pad.copy(), pad.copy(), pad.copy()
            for k, j in enumerate(order):
                cores, _, ufp, p95e = group[j]
                cores_a[k], uf_a[k], p95_a[k] = cores, ufp, p95e
            mem_a = cores_a * GB_PER_CORE
            valid = np.arange(SERVE_GROUP_PAD) < n
            # trace/run the scan in x64: bit-equivalent to the f64 host
            # rule, so 'serve' reproduces 'event' placements exactly
            # (the f32 serving path's divergence is bounded in
            # DESIGN.md §9)
            # the controller's ratio (stepped just above, one scan
            # behind by construction) widens or shrinks the watt
            # ceilings for THIS group's scan; the diurnal ratchet does
            # the same to the cores/GB axes from the trough sample
            # (watts never ratchet — the breaker limit is physical).
            # The watt multiply stays in f32 like the scalar era, so a
            # watt-only budget reproduces those decisions bit for bit.
            ratio = 1.0 if adp is None else adp.ratio
            rrat = trough_ratios(float(tel.diurnal_util(t))) \
                if sv.diurnal_ratchet else np.ones(N_RESOURCES)
            cap_mult = np.asarray([ratio, rrat[1], rrat[2]], np.float32)
            with jax.enable_x64(True), span("place"):
                if backend_name == "serve":
                    if obs is not None:
                        obs.registry.counter(
                            "serve_dispatch_total",
                            help="compiled kernel dispatches, "
                            "by call site", kind="place_batch").inc()
                    _, srvs = place_batch(
                        device_state(state, jnp.float64,
                                     mem_gb=mem_chassis,
                                     mem_nuf=mem_nuf_chassis), cores_a,
                        uf_a.astype(bool), p95_a, valid,
                        serve_res_cap * cap_mult,
                        policy, state.cores_per_server, mem_gb=mem_a)
                    chosen = [int(s) for s in np.asarray(srvs)[:n]]
                else:
                    # the token pool is the global allowance net of
                    # everything currently committed — per resource
                    # axis — so the budget invariant holds across the
                    # whole run, not just within one group; the
                    # adaptive ratio retargets the watt allowance but
                    # never the committed side (`serve.adaptive.
                    # retarget_pool` semantics)
                    committed_vec = np.array([
                        float(state.rho_peak.sum()),
                        n_servers * float(CORES_PER_BLADE)
                        - float(state.free_cores.sum()),
                        float(mem_chassis.sum())])
                    pool_mult = np.array([ratio, rrat[1], rrat[2]])
                    pool = None if not pool_finite.any() else np.where(
                        pool_finite,
                        np.maximum(serve_pool_total * pool_mult
                                   - committed_vec, 0.0), np.inf)
                    sharded = shard_state(
                        device_state(state, jnp.float64,
                                     mem_gb=mem_chassis,
                                     mem_nuf=mem_nuf_chassis),
                        sv.shards, rho_cap=serve_res_cap * cap_mult,
                        pool_total=pool)
                    _, srvs, info = place_group_sharded(
                        sharded, cores_a, uf_a.astype(bool), p95_a,
                        valid, policy, state.cores_per_server,
                        mem_gb=mem_a,
                        registry=None if obs is None else obs.registry)
                    # per-resource token conservation, asserted on
                    # every scan: the pool delta each finite axis
                    # reports must equal the summed demand of the VMs
                    # it admitted (nothing minted, nothing leaked)
                    if pool is not None:
                        adm = (np.asarray(srvs) >= 0) & valid
                        admitted_vec = np.array([
                            float((p95_a * cores_a)[adm].sum()),
                            float(cores_a[adm].sum()),
                            float(mem_a[adm].sum())])
                        drawn = np.asarray(info["tokens_drawn_vec"])
                        assert np.allclose(
                            drawn[pool_finite],
                            admitted_vec[pool_finite],
                            rtol=1e-9, atol=1e-6), \
                            "per-resource token conservation violated: " \
                            f"drawn={drawn} admitted={admitted_vec}"
                    chosen = [None] * n        # un-permute the merge
                    for k, j in enumerate(order):
                        chosen[j] = int(srvs[k])
        else:
            chosen = None
        for i, (cores, life_h, uf_pred, p95_eff) in enumerate(group):
            srv = chosen[i] if chosen is not None else \
                policy.choose(state, cores, uf_pred)
            placements += 1
            if trace is not None:
                trace.append(-1 if srv is None else int(srv))
            if srv is None or srv < 0:
                failures += 1
                continue
            mem = cores * GB_PER_CORE
            state.place(srv, cores, p95_eff, uf_pred)
            mem_chassis[chassis_of[srv]] += mem
            if not uf_pred:
                mem_nuf_chassis[chassis_of[srv]] += mem
            vm_live[token] = (srv, cores, p95_eff, uf_pred, mem)
            heapq.heappush(departures, (t + life_h, token))
            token += 1

    power = None
    if spec.power is not None and vm_live:
        power = evaluate_power_dynamics(
            vm_live, chassis_of, state.n_chassis, spec.power.budget_w,
            sample_chassis=spec.power.chassis,
            duration_s=spec.power.duration_s, seed=spec.seed,
            backend=spec.power.backend)
    throttled = np.zeros(2)
    if emer is not None:
        from repro.serve.emergency import throttled_by_level
        throttled = throttled_by_level(emer.st)
    metrics = SimMetrics(
        failure_rate=failures / max(placements, 1),
        empty_server_ratio=float(np.mean(empty_samples)),
        chassis_score_std=float(np.mean(chassis_stds)),
        server_score_std=float(np.mean(server_stds)),
        placements=placements, failures=failures, power=power,
        throttled_s=np.asarray(throttled, np.float64),
        alarms=0 if emer is None else emer.alarms,
        migrations=0 if emer is None else emer.migrations,
        balloon_events=0 if emer is None else emer.balloon_events,
        balloon_reclaimed_gb=0.0 if emer is None
        else emer.balloon_reclaimed_gb,
        ballooned_gb=0.0 if emer is None or emer.bst is None
        else float(np.asarray(emer.bst.ballooned_gb).sum()),
        adaptive_ratio=1.0 if adp is None else adp.ratio,
        adaptive_ratchets=0 if adp is None else adp.ratchets,
        adaptive_backoffs=0 if adp is None else adp.backoffs,
        crit_confusion=crit_cm, p95_confusion=p95_cm)
    if obs is not None:
        from repro.obs import record_sim_metrics
        record_sim_metrics(obs.registry, metrics)
    return metrics


def fig7_sweep(alphas=(0.0, 0.2, 0.4, 0.6, 0.8, 1.0), days: float = 30.0,
               seed: int = 0, deployments_per_hour: float = 8.0) -> dict:
    """Fig 7: NoRule baseline + {ml, oracle, crit_only} x alpha sweep."""
    def run(pol, mode):
        return simulate(pol, PredictionChannel(mode), SimSpec(
            days=days, seed=seed,
            deployments_per_hour=deployments_per_hour))
    out = {"NoRule": run(SchedulerPolicy(use_power_rule=False), "none")}
    for mode in ("ml", "oracle", "crit_only"):
        for a in alphas:
            pol = SchedulerPolicy(
                alpha=a,
                use_utilization_predictions=(mode != "crit_only"))
            out[f"{mode}:alpha={a}"] = run(pol, mode)
    return out
