"""Online prediction-and-admission serving pipeline (paper §II-D).

`ServePipeline` is the device-resident Resource-Central path from
arrival stream to placement decision: a micro-batching ingest queue
feeds one compiled flow per batch —

    featurize (serve.featurizer)  ->  two-stage inference + gating
    (serve.inference)  ->  Algorithm-1 scoring with fused power
    admission (serve.placement / serve.admission)

with all model operands, subscription aggregates, and cluster
aggregates living on device between batches. The paper's daily retrain
maps to `hot_swap`: the new forest is packed into the standby model
buffer while the active one keeps serving, then an atomic flip routes
the next batch to it — no arrival is dropped and no recompilation
happens (retrained forests share shapes, so the serving jits are
already specialized).

`ShardedServePipeline` swaps the placement stage for the sharded
consistent-placement protocol (`serve.sharding`) when the cluster is
partitioned over a device mesh, and on a mesh runs inference with the
batch split over the devices — every other stage is shard-agnostic
and shared.

Arrivals and departures enter through the cross-host ingest subsystem
(`serve.ingest`, DESIGN.md §11): each ingest host owns its own stamped
queue and a deterministic watermark-based timestamp merge produces the
micro-batches. `submit`/`depart` are the 1-host special case;
`submit_to`/`depart_to` are the per-host path.
"""
from __future__ import annotations

import contextlib
import time
import warnings
from dataclasses import dataclass, field, replace
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import features
from repro.core.placement import SchedulerPolicy
from repro.core.power_model import ServerPowerModel
from repro.core.predictor import UF, PredictionService
from repro.core.resources import N_RESOURCES, RESOURCES, ResourceVector
from repro.obs import LEVEL_NAMES, Observability
from repro.serve import (admission, adaptive, ballooning, emergency,
                         placement, sharding)
from repro.serve.featurizer import (
    SubscriptionTable, featurize_batch, ingest_population, shard_table,
    table_from_history)
from repro.serve.inference import (
    bucket_to_p95_jnp, pack_service, resolve_kernel, served_query)
from repro.serve.ingest import (
    ARRIVAL, CAPPING, CapBatch, DepartureBatch, IngestMux, MergedEvents,
    slice_soa)
from repro.sim.telemetry import ArrivalBatch, Population


@dataclass(frozen=True)
class PlaneBundle:
    """Every control-plane attachment of a pipeline, in one field
    (DESIGN.md §16) — what used to sprawl across five constructor
    kwargs (``chassis_budget_w``, ``cluster_budget_w``,
    ``emergency_cfg``, ``adaptive_cfg``, ``obs``), now carried by
    `ServeConfig.planes` so a pipeline's whole wiring is one value you
    can name, log, and reuse.

    chassis_budget: per-chassis admission budget as a `ResourceVector`
        — the watts axis converts through the power model into the
        legacy rho ceiling, the cores/GB axes are ledger currency
        (`serve.admission.resource_caps_from_budget`); a power-only
        vector reproduces ``chassis_budget_w`` bit for bit.
    cluster_budget: sharded pipelines only — the global token-pool
        budget (`serve.sharding.resource_pool_from_budget`); a
        power-only vector reproduces ``cluster_budget_w``.
    emergency / adaptive / ballooning: the emergency-capping plane,
        the closed-loop oversubscription controller, and the memory
        ballooning rung between them and migration (ballooning
        requires emergency — its probe reuses the alarm arithmetic).
    obs: the observability plane (decision-neutral, host-side)."""
    chassis_budget: ResourceVector | None = None
    cluster_budget: ResourceVector | None = None
    emergency: emergency.EmergencyConfig | None = None
    adaptive: adaptive.AdaptiveConfig | None = None
    ballooning: ballooning.BallooningConfig | None = None
    obs: Observability | None = None


@dataclass(frozen=True)
class ServeConfig:
    batch_size: int = 256
    kernel: str = "auto"            # 'pallas' | 'ref' | 'auto'
    policy: SchedulerPolicy = field(default_factory=SchedulerPolicy)
    n_ingest_hosts: int = 1         # per-host queues (serve.ingest)
    planes: PlaneBundle = field(default_factory=PlaneBundle)


@dataclass
class ServeResult:
    """Per-arrival decisions for one served batch (host arrays)."""
    server: np.ndarray              # (B,) int32; FAIL_* codes on reject
    workload_type: np.ndarray       # (B,) post-gating UF/NUF
    p95_bucket: np.ndarray          # (B,) post-gating bucket
    p95_eff: np.ndarray             # (B,) p95 recorded into aggregates
    conservative: np.ndarray        # (B,) bool — hit a confidence gate

    @property
    def admitted(self) -> np.ndarray:
        return self.server >= 0

    @property
    def n_admitted(self) -> int:
        return int(self.admitted.sum())

    @property
    def n_capacity_rejected(self) -> int:
        return int((self.server == placement.FAIL_CAPACITY).sum())

    @property
    def n_power_rejected(self) -> int:
        return int((self.server == placement.FAIL_POWER).sum())

    @property
    def n_token_rejected(self) -> int:
        """Rejections by an exhausted shard power-token pool — only the
        sharded pipeline under a `cluster_budget_w` produces these.
        admitted + capacity + power + token == batch size."""
        return int((self.server == placement.FAIL_TOKENS).sum())

    @property
    def n_conservative(self) -> int:
        return int(self.conservative.sum())


def _concat_results(parts: list) -> ServeResult:
    return ServeResult(*(np.concatenate([getattr(p, f) for p in parts])
                         for f in ("server", "workload_type", "p95_bucket",
                                   "p95_eff", "conservative")))


def _concat_batches(parts: list) -> ArrivalBatch:
    return ArrivalBatch(*(np.concatenate([getattr(p, f) for p in parts])
                          for f in ArrivalBatch.__dataclass_fields__))


@lru_cache(maxsize=None)
def _adaptive_step_fn(cfg: adaptive.AdaptiveConfig):
    """Compiled unsharded adaptive-controller scan: per-chassis
    criticality aggregates from the cluster state, then the masked
    stability-scoring + ratio step (`serve.adaptive.adaptive_step`)."""

    def fn(gamma_nuf, gamma_uf, chassis_servers, ast, pw, mask):
        rho_lv = emergency.chassis_rho_levels(gamma_nuf, gamma_uf,
                                              chassis_servers, jnp)
        return adaptive.adaptive_step(cfg, ast, rho_lv, pw, mask, jnp)

    return jax.jit(fn)


@lru_cache(maxsize=None)
def _cap_step_fn(cfg: emergency.EmergencyConfig):
    """Compiled unsharded emergency scan: per-chassis criticality
    aggregates from the cluster state, then the masked alarm +
    apportionment step (`serve.emergency.masked_step`)."""

    def fn(gamma_nuf, gamma_uf, chassis_servers, emer, pw, mask, ts):
        rho_lv = emergency.chassis_rho_levels(gamma_nuf, gamma_uf,
                                              chassis_servers, jnp)
        return emergency.masked_step(cfg, emer, rho_lv, pw, mask, ts,
                                     jnp)

    return jax.jit(fn)


@lru_cache(maxsize=None)
def _balloon_cap_step_fn(ecfg: emergency.EmergencyConfig,
                         bcfg: ballooning.BallooningConfig):
    """Compiled unsharded balloon-then-cap scan: the ballooning rung
    (`serve.ballooning.balloon_step` over the chassis NUF-memory
    ledger) absorbs what the NUF frequency floor cannot, and the
    masked emergency step consumes the DRAM-adjusted draws."""

    def fn(gamma_nuf, gamma_uf, chassis_servers, mem_nuf, emer, bst,
           pw, mask, ts):
        rho_lv = emergency.chassis_rho_levels(gamma_nuf, gamma_uf,
                                              chassis_servers, jnp)
        bst2, bout = ballooning.balloon_step(
            bcfg, ecfg, bst, rho_lv, pw, mem_nuf, mask, jnp)
        emer2, eout = emergency.masked_step(
            ecfg, emer, rho_lv, bout.power_adj_w, mask, ts, jnp)
        return emer2, bst2, eout, bout

    return jax.jit(fn)


#: Row counts a gathered departure dispatch is padded to (the smallest
#: that holds the buffer); a larger buffer goes in chunks of the
#: largest. Every size compiles once, when the pipeline is built.
DEPART_LADDER = (64, 256, 1024, 4096)


@jax.jit
def _remove_gathered(state, servers, block):
    """`placement.remove_batch` of one padded block of gathered
    departures: (N,) servers (-1 rows are ignored) and the (4, N) stack
    of cores, p95_eff, is_uf and mem_gb in the state's dtype, split
    here so the host makes two copies instead of five."""
    cores, p95_eff, is_uf, mem_gb = block
    return placement.remove_batch(state, servers, cores, p95_eff, is_uf,
                                  mem_gb=mem_gb)


#: Sentinel distinguishing "kwarg not passed" from an explicit None on
#: the deprecated constructor kwargs.
_UNSET = object()


def _legacy_planes(planes: PlaneBundle, what: str,
                   **kw) -> PlaneBundle:
    """Fold deprecated constructor kwargs into the `PlaneBundle`,
    warning once per call site. Tier-1 runs with
    ``-W error::DeprecationWarning``, so every in-repo caller uses the
    `ServeConfig.planes` front door — the shim exists for external
    callers and for the equivalence tests that pin old == new."""
    given = {k: v for k, v in kw.items() if v is not _UNSET}
    if not given:
        return planes
    warnings.warn(
        f"{', '.join(sorted(given))} as {what} constructor kwargs are "
        "deprecated; pass ServeConfig(planes=PlaneBundle(...)) "
        "(docs/resources.md has the migration table)",
        DeprecationWarning, stacklevel=3)
    fields = {}
    if "chassis_budget_w" in given:
        w = given.pop("chassis_budget_w")
        fields["chassis_budget"] = \
            None if w is None else ResourceVector(watts=float(w))
    if "cluster_budget_w" in given:
        w = given.pop("cluster_budget_w")
        fields["cluster_budget"] = \
            None if w is None else ResourceVector(watts=float(w))
    for old, new in (("emergency_cfg", "emergency"),
                     ("adaptive_cfg", "adaptive"), ("obs", "obs")):
        if old in given:
            fields[new] = given.pop(old)
    return replace(planes, **fields)


def _unique_chassis_windows(chassis: np.ndarray):
    """Split one merged CAPPING run into maximal prefixes with unique
    chassis ids, preserving order: the dense masked kernel applies one
    sample per chassis per call, so a window that samples a chassis
    twice becomes two sequential windows (hysteresis clocks see both,
    in merged order)."""
    lo, seen = 0, set()
    for i, c in enumerate(chassis):
        c = int(c)
        if c in seen:
            yield lo, i
            lo, seen = i, set()
        seen.add(c)
    if lo < len(chassis):
        yield lo, len(chassis)


class ServePipeline:
    """Stateful serving endpoint. Not thread-safe; one instance serves
    one cluster from one host — `ShardedServePipeline` is the
    multi-host/device path (DESIGN.md §10, docs/sharding.md)."""

    def __init__(self, service: PredictionService,
                 table: SubscriptionTable,
                 state: placement.DeviceClusterState,
                 cores_per_server: int,
                 config: ServeConfig | None = None,
                 chassis_budget_w=_UNSET,
                 power_model: ServerPowerModel | None = None,
                 blades_per_chassis: int | None = None,
                 emergency_cfg=_UNSET,
                 obs=_UNSET,
                 adaptive_cfg=_UNSET):
        config = config or ServeConfig()
        planes = _legacy_planes(config.planes, type(self).__name__,
                                chassis_budget_w=chassis_budget_w,
                                emergency_cfg=emergency_cfg, obs=obs,
                                adaptive_cfg=adaptive_cfg)
        if planes.ballooning is not None and planes.emergency is None:
            raise ValueError(
                "PlaneBundle.ballooning requires PlaneBundle.emergency "
                "— the ballooning rung probes the emergency plane's "
                "alarm arithmetic to size its reclaim")
        self.config = replace(config, planes=planes)
        self.table = table
        # live departure rows not yet applied to the state: (servers,
        # (4, n) float block) per run, applied in one dispatch before
        # the next read of the aggregates (`_flush_departures`)
        self._pending_departures: list[tuple] = []
        self.state = state
        # observability plane (repro.obs, DESIGN.md §14) — purely
        # host-side consumers of outputs the kernels already produce,
        # so obs on/off never changes a decision
        self.obs = planes.obs
        self._tracer = None if self.obs is None else self.obs.tracer
        # ingest watermark (stamp of the newest drained merged run) —
        # the clock the windows/SLO/recorder pillars (DESIGN.md §17)
        # aggregate on; stays 0.0 until the first streamed event
        self._watermark = 0.0
        # direct serve() calls bypass the ingest merge, so their
        # decisions are not replayable — the flight recorder skips
        # them while this flag is up
        self._recorder_suspended = False
        self._batches = 0
        self._has_pool = False      # sharded subclass may flip this
        self._chassis_of_host = np.asarray(state.chassis_of)
        self._rule_idx = self._policy_rule_index(self.config.policy)
        self.cores_per_server = int(cores_per_server)
        self._kernel = resolve_kernel(self.config.kernel)
        # double-buffered model: index _active serves, 1-_active packs
        self._buffers = [pack_service(service), None]
        self._active = 0
        n_chassis = state.rho_max.shape[0]
        self.n_chassis = n_chassis
        if blades_per_chassis is None:
            blades_per_chassis = state.n_servers // n_chassis
        self.blades_per_chassis = blades_per_chassis
        self.power_model = power_model or ServerPowerModel()
        # (C, R) per-chassis admission ceilings over the joint
        # (watts, cores, GB) ledger (DESIGN.md §16); a power-only (or
        # absent) budget leaves the cores/GB columns +inf — vacuous,
        # decision-identical to the scalar watt ceiling
        self.res_cap = jnp.asarray(admission.resource_caps_from_budget(
            planes.chassis_budget or ResourceVector(),
            blades_per_chassis, n_chassis, self.power_model))
        if self.config.n_ingest_hosts < 1:
            raise ValueError(
                f"n_ingest_hosts must be >= 1, "
                f"got {self.config.n_ingest_hosts}")
        # with a tracer, ingest keeps each event's push time: the
        # `queue` wait span of a batch starts at its oldest arrival's
        self.ingest = IngestMux(
            self.config.n_ingest_hosts,
            clock=None if self._tracer is None else self._tracer.clock)
        self._pending: list[ArrivalBatch] = []   # merged, awaiting batch
        self._pending_pushed: list[np.ndarray] = []  # their push times
        self._queued = 0
        self.swaps = 0
        self.served = 0
        # power-emergency plane (serve.emergency, DESIGN.md §12)
        self.emergency_cfg = planes.emergency
        self._pending_caps: list[tuple] = []    # queued (chassis, pw, t)
        self.emergency = None
        self._alarms = 0
        # counters of a fused sweep, fetched with its batch's decisions
        self._sweep = None          # (SweepCounters, windows) or None
        self._cap_epoch = None      # first cap stamp; rebases clocks
        if self.emergency_cfg is not None:
            ecfg = self.emergency_cfg
            if ecfg.blades_per_chassis != self.blades_per_chassis:
                raise ValueError(
                    f"emergency_cfg.blades_per_chassis="
                    f"{ecfg.blades_per_chassis} does not match "
                    f"the pipeline's {self.blades_per_chassis} — the "
                    "static chassis floor (and every alarm and cut) "
                    "would be miscalibrated")
            self.emergency = self._init_emergency()
        # ballooning rung (serve.ballooning, DESIGN.md §16): fires on
        # the same CAPPING samples, between the NUF frequency floor and
        # migration
        self._balloon = None
        if planes.ballooning is not None:
            self._balloon = self._init_ballooning()
        # adaptive oversubscription controller (serve.adaptive,
        # DESIGN.md §15): CAPPING samples feed per-chassis stability
        # windows; the stepped ratio rescales the admission ceiling
        # (and, sharded, the free token pools) between micro-batches
        self.adaptive_cfg = planes.adaptive
        self._adaptive = None
        self._res_cap_base = self.res_cap
        # (R,) time-of-day conditioning multipliers
        # (`core.resources.trough_ratios`; watts axis pinned at 1.0 —
        # the breaker limit never ratchets); `set_resource_ratios`
        # installs a fresh sample
        self._res_ratios = np.ones(N_RESOURCES)
        self._ratio_dev = None      # adaptive ratio, device scalar
        self._ratio_prev = 1.0
        if self.adaptive_cfg is not None:
            acfg = self.adaptive_cfg
            if acfg.blades_per_chassis != self.blades_per_chassis:
                raise ValueError(
                    f"adaptive_cfg.blades_per_chassis="
                    f"{acfg.blades_per_chassis} does not match "
                    f"the pipeline's {self.blades_per_chassis} — power "
                    "samples would read back as the wrong utilization")
            self._adaptive = self._init_adaptive()
        self._warm_departures()

    @property
    def state(self) -> placement.DeviceClusterState:
        """The cluster's aggregates on the device. Reading them first
        applies the departures gathered since the last read, so every
        reader sees each departure pushed so far."""
        if self._pending_departures:
            self._flush_departures()
        return self._state

    @state.setter
    def state(self, value):
        self._state = value

    @property
    def rho_cap(self):
        """(C,) watt-axis admission ceiling (rho units) — the legacy
        scalar view of the (C, R) `res_cap` ledger ceiling."""
        return self.res_cap[..., 0]

    def _init_ballooning(self):
        """Fresh all-deflated balloon state (unsharded layout)."""
        return ballooning.init_ballooning(
            self.n_chassis, xp=jnp, dtype=self.state.free_cores.dtype)

    def _init_emergency(self):
        """Fresh per-chassis emergency state (unsharded layout)."""
        return emergency.init_emergency(
            self.n_chassis, xp=jnp,
            dtype=self.state.free_cores.dtype)

    @property
    def emergency(self):
        """Current emergency-plane state. Reading it flushes any cap
        sub-windows still queued for fusion, so observers always see
        the state as of the last event pushed — queueing is a pure
        dispatch-count optimization, never a semantic lag."""
        self._flush_caps()
        return self._emergency

    @emergency.setter
    def emergency(self, value):
        self._emergency = value

    @property
    def alarms(self) -> int:
        """Cumulative alarm count across all applied sample windows
        (flushes queued windows first, like `emergency`)."""
        self._flush_caps()
        return self._alarms

    # -- adaptive oversubscription (serve.adaptive, DESIGN.md §15) ---------
    def _init_adaptive(self):
        """Fresh controller state (unsharded layout, ratio 1.0)."""
        return adaptive.init_adaptive(
            self.adaptive_cfg, self.n_chassis, xp=jnp,
            dtype=self.state.free_cores.dtype)

    @property
    def adaptive_state(self):
        """Current adaptive-controller state (None with the controller
        off). Unlike `emergency` there is nothing to flush — the
        controller steps eagerly when CAPPING events are consumed, so
        its ratio is already in force for the next micro-batch."""
        return self._adaptive

    @property
    def adaptive_ratio(self):
        """Current oversubscription ratio (1.0 with the controller
        off); the sharded pipeline returns the (N,) per-shard ratios."""
        if self._adaptive is None:
            return 1.0
        return float(np.asarray(self._adaptive.ratio))

    def _adaptive_scan(self, chassis, power_w) -> None:
        """Run one controller scan over a unique-chassis sample window
        and put the stepped ratio in force (unsharded path)."""
        dtype = self.state.free_cores.dtype
        pw, mask, _ = emergency.scatter_samples(
            self.n_chassis, chassis, power_w,
            np.zeros(len(np.asarray(chassis))), jnp, dtype)
        if self.obs is not None:
            self.obs.registry.counter(
                "serve_dispatch_total",
                help="compiled kernel dispatches, by call site",
                kind="adaptive_step").inc()
        fn = _adaptive_step_fn(self.adaptive_cfg)
        self._adaptive, out = fn(self.state.gamma_nuf,
                                 self.state.gamma_uf,
                                 self.state.chassis_servers,
                                 self._adaptive, pw, mask)
        self._apply_ratio(out)

    def _apply_ratio(self, out) -> None:
        """Rescale the effective watt budget to the stepped ratio —
        unsharded, that is the watts axis of the per-chassis admission
        ceiling (the device-side product keeps the scan sync-free when
        obs is off). With ``adaptive_cfg.hold_on_stale`` the *applied*
        ratio is additionally clamped to ``ratio_min`` while the
        prediction scorecard reports `model_stale`
        (`serve.adaptive.gate_ratio_on_stale`) — the controller state
        is untouched, so the ratio resumes when the model scores
        fresh; off by default, preserving obs on/off bit-identity."""
        self._ratio_dev = out.ratio
        if (self.adaptive_cfg is not None
                and self.adaptive_cfg.hold_on_stale
                and self.obs is not None
                and self.obs.quality is not None):
            self._ratio_dev = adaptive.gate_ratio_on_stale(
                self.adaptive_cfg, self._fetch(out.ratio),
                self.obs.quality.model_stale)
        self._refresh_caps()
        self._record_adaptive(out)

    def _axis_mult(self, dtype) -> jnp.ndarray:
        """(R,) effective per-axis ceiling multiplier: the adaptive
        controller's ratio on the watts axis times the diurnal
        conditioning on the cores/GB axes. Both default to exact 1.0,
        so with neither plane active the base ceiling passes through
        bit-for-bit (IEEE multiply by 1.0 is the identity)."""
        one = jnp.ones((), dtype)
        r = one if self._ratio_dev is None \
            else jnp.asarray(self._ratio_dev, dtype)
        return jnp.stack([r, one, one]) \
            * jnp.asarray(self._res_ratios, dtype)

    def _refresh_caps(self) -> None:
        """Recompute the effective admission ceiling from the base
        ceiling and the current per-axis multipliers (unsharded; the
        sharded override also retargets the token pools)."""
        self.res_cap = self._res_cap_base \
            * self._axis_mult(self._res_cap_base.dtype)

    def set_resource_ratios(self, ratios) -> None:
        """Install a fresh (R,) time-of-day conditioning sample
        (`core.resources.trough_ratios` of the current diurnal
        utilization): the cores/GB axes of every admission ceiling
        (and, sharded, token pool) rescale immediately — Coach-style
        ratcheting on the trough. The watts axis must be exactly 1.0
        (a breaker budget is a physical limit, never conditioned)."""
        ratios = np.asarray(ratios, np.float64)
        if ratios.shape != (N_RESOURCES,):
            raise ValueError(
                f"ratios must be ({N_RESOURCES},) over {RESOURCES}, "
                f"got shape {ratios.shape}")
        if ratios[0] != 1.0:
            raise ValueError(
                f"ratios[0] (watts) must be 1.0, got {ratios[0]} — "
                "the watt budget is a breaker limit and never "
                "ratchets (core.resources.trough_ratios pins it)")
        self._res_ratios = ratios
        self._refresh_caps()

    def _record_adaptive(self, out) -> None:
        """Export one controller decision: ratio gauge, step counters,
        and an `obs.audit.AdaptiveTrail` reason row — host-side
        consumers of outputs the kernel already returned."""
        if self.obs is None:
            return
        reg = self.obs.registry
        out = self._fetch(out)
        r = float(out.ratio)
        reg.gauge("adaptive_ratio",
                  help="oversubscription ratio of the adaptive "
                  "controller").set(r)
        reg.counter("adaptive_ratchet_total",
                    help="adaptive-controller up-steps taken").inc(
                        int(out.ratchet))
        reg.counter("adaptive_backoff_total",
                    help="adaptive-controller down-steps taken").inc(
                        int(out.backoff))
        if self.obs.adaptive is not None:
            ratchet, backoff = bool(out.ratchet), bool(out.backoff)
            self.obs.adaptive.record(
                t=time.time(), shard=-1, ratio=r,
                stable_frac=float(out.stable_frac),
                n_known=int(out.n_known), n_stable=int(out.n_stable),
                action=1 if ratchet else (-1 if backoff else 0),
                reason=adaptive.decision_reason(
                    self._ratio_prev, r, int(out.n_known),
                    ratchet, backoff, bool(out.hot)))
        self._ratio_prev = r

    # -- observability (repro.obs, DESIGN.md §14) --------------------------
    @staticmethod
    def _policy_rule_index(policy: SchedulerPolicy) -> int:
        """Admission-rule index recorded into the audit trail: 0 =
        packing rule only (NoRule baseline), 1 = power rule only, 2 =
        combined weighted aggregation (the paper's default)."""
        if not policy.use_power_rule or policy.power_weight == 0:
            return 0
        if policy.packing_weight == 0:
            return 1
        return 2

    def _span(self, name: str):
        """Span context for one pipeline stage (no-op without obs)."""
        if self.obs is not None:
            return self.obs.span(name)
        return contextlib.nullcontext()

    def _fetch(self, tree):
        """Read a pytree of device arrays to the host in one
        `jax.device_get`, under a ``fetch`` span: every host read of a
        device value on the unsharded serve path goes through here."""
        with self._span("fetch"):
            return jax.device_get(tree)

    @contextlib.contextmanager
    def _push(self):
        """One push call: the spans it closes outside a batch's
        service are attributed to the first micro-batch it serves
        (they stay -1 when it serves none)."""
        tr = self._tracer
        if tr is None:
            yield
            return
        since, first = tr.mark(), self._batches + 1
        yield
        if self._batches >= first:
            tr.claim(since, first)

    def _pool_tokens_left(self) -> float:
        """Remaining power tokens recorded into audit rows (+inf when
        no cluster watt budget bounds admission — the unsharded
        pipeline and unbudgeted sharded pipelines)."""
        return float("inf")

    def _record_batch(self, batch: ArrivalBatch, res: ServeResult,
                      raw=None) -> None:
        """Fold one served batch's decisions into the metrics registry,
        audit trail, and the §17 pillars (windows / scorecard / flight
        recorder) — a pure host-side reduction of outputs the
        placement kernel already returned (`placement.
        outcome_counters`, plus the raw head outputs fetched alongside
        when the quality pillar is on), so recording can never perturb
        a decision."""
        if self.obs is None:
            return
        reg = self.obs.registry
        b = len(res.server)
        valid = np.ones(b, bool)
        cnt = placement.outcome_counters(
            res.server, valid, np.asarray(batch.cores), res.p95_eff,
            mem_gb=np.asarray(batch.memory_gb))
        reg.counter("serve_batches_total",
                    help="micro-batches served").inc()
        reg.counter("serve_arrivals_total",
                    help="arrivals decided").inc(b)
        reg.counter("serve_admits_total",
                    help="arrivals admitted").inc(cnt["admits"])
        for reason, key in (("capacity", "fail_capacity"),
                            ("power", "fail_power"),
                            ("tokens", "fail_tokens")):
            reg.counter("serve_rejects_total",
                        help="arrivals rejected, by reason",
                        reason=reason).inc(cnt[key])
        reg.counter("serve_conservative_total",
                    help="decisions that hit a confidence gate").inc(
                        res.n_conservative)
        reg.counter("serve_rho_admitted_total",
                    help="admitted sum(p95*cores), rho units").inc(
                        cnt["rho_admitted"])
        reg.counter("serve_cores_admitted_total",
                    help="admitted virtual cores").inc(
                        cnt["cores_admitted"])
        reg.counter("serve_gb_admitted_total",
                    help="admitted memory, GB").inc(cnt["gb_admitted"])
        if self.obs.audit is not None:
            srv = np.asarray(res.server)
            chassis = np.where(
                srv >= 0, self._chassis_of_host[np.maximum(srv, 0)], -1)
            self.obs.audit.record_batch(
                t=time.time(), batch=self._batches, servers=srv,
                chassis=chassis, rule=self._rule_idx,
                cores=np.asarray(batch.cores),
                is_uf=res.workload_type == UF, p95_eff=res.p95_eff,
                valid=valid, conservative=res.conservative,
                pool_left=self._pool_tokens_left())
        if self.obs.windows is not None:
            w, t = self.obs.windows, self._watermark
            w.observe(t, "arrivals", n=b)
            if cnt["admits"]:
                w.observe(t, "admits", n=int(cnt["admits"]))
            if b - cnt["admits"]:
                w.observe(t, "rejects", n=int(b - cnt["admits"]))
            if res.n_conservative:
                w.observe(t, "conservative", n=int(res.n_conservative))
            w.observe(t, "rho_admitted", float(cnt["rho_admitted"]))
        if self.obs.quality is not None and raw is not None:
            self.obs.quality.record(
                true_crit=np.asarray(batch.user_facing, np.int64),
                true_bucket=np.asarray(
                    features.p95_bucket(np.asarray(batch.p95_util)),
                    np.int64),
                crit_used=res.workload_type,
                bucket_used=res.p95_bucket,
                crit_raw=raw[0], crit_conf=raw[1],
                bucket_raw=raw[2], bucket_conf=raw[3],
                conservative=res.conservative)
        if (self.obs.recorder is not None
                and not self._recorder_suspended):
            self.obs.recorder.record_decision(
                np.asarray(res.server), self._watermark)
        self._obs_tick()

    def _obs_tick(self) -> None:
        """Advance the watermark-clock pillars (DESIGN.md §17): close
        tumbling windows the watermark passed, re-sample the SLO
        monitor from the registry counters, and evaluate the
        burn-rate alerts. Host-side only; no-op for pillars that are
        off."""
        if self.obs is None:
            return
        if self.obs.windows is not None:
            self.obs.windows.advance(self._watermark)
        if self.obs.slo is not None:
            self.obs.slo.sample(self._watermark, self.obs.registry)
            self.obs.slo.evaluate(self._watermark)

    def _record_sweep(self, sweep: placement.SweepCounters,
                      windows: int) -> None:
        """Fold one emergency sweep's in-scan counters into the
        registry. `windows` is host-tracked (the device struct cannot
        carry it — summing per-shard copies would overcount)."""
        if self.obs is None:
            return
        reg = self.obs.registry
        reg.counter("emergency_cap_windows_total",
                    help="cap sample windows applied").inc(windows)
        reg.counter("emergency_samples_total",
                    help="chassis power samples consumed").inc(
                        int(np.asarray(sweep.samples)))
        reg.counter("emergency_alarms_total",
                    help="power-emergency alarms raised").inc(
                        int(np.asarray(sweep.alarms)))
        cut_w = float(np.asarray(sweep.cut_w))
        reg.counter("emergency_cut_watts_total",
                    help="watts of reduction demanded past the "
                    "target").inc(cut_w)
        reg.counter("emergency_leftover_watts_total",
                    help="demanded watts no frequency floor could "
                    "absorb (RAPL backstop)").inc(
                        float(np.asarray(sweep.leftover_w)))
        if cut_w > 0.0:
            reg.histogram("emergency_cut_watts",
                          help="watts of cut demanded per sweep"
                          ).observe(cut_w)
        for level, w in zip(LEVEL_NAMES,
                            np.asarray(sweep.cut_by_level_w, np.float64)):
            reg.counter("emergency_level_cut_watts_total",
                        help="watts actually removed, by criticality "
                        "level",
                        level=level).inc(float(w))
        alarms = int(np.asarray(sweep.alarms))
        if self.obs.windows is not None:
            wp, t = self.obs.windows, self._watermark
            if alarms:
                wp.observe(t, "alarms", n=alarms)
            if cut_w > 0.0:
                wp.observe(t, "cut_watts", cut_w)
                wp.observe_hist("cut_watts", cut_w, lo=0.0, hi=2.0e4)
        if self.obs.quality is not None:
            self.obs.quality.observe_alarms(
                alarms, cut_w=cut_w,
                samples=int(np.asarray(sweep.samples)))
        if self.obs.recorder is not None and alarms:
            self.obs.recorder.mark_incident(
                self._watermark, alarms,
                {k: reg.value(k) for k in (
                    "emergency_alarms_total",
                    "emergency_cut_watts_total",
                    "emergency_leftover_watts_total",
                    "serve_arrivals_total")})
        self._obs_tick()

    # -- construction ------------------------------------------------------
    @classmethod
    def from_history(cls, service: PredictionService, history: Population,
                     uf_labels: np.ndarray, n_servers: int,
                     cores_per_server: int, blades_per_chassis: int,
                     table_capacity: int | None = None, **kw):
        """Bootstrap table + empty cluster from an offline labeled
        history (the state a daily retrain hands the serving job)."""
        if table_capacity is None:
            table_capacity = max(
                (v.subscription for v in history.vms), default=0) + 1024
        table = table_from_history(history, uf_labels, table_capacity)
        chassis_of = np.arange(n_servers) // blades_per_chassis
        state = placement.fresh_state(n_servers, cores_per_server,
                                      chassis_of)
        return cls(service, table, state, cores_per_server,
                   blades_per_chassis=blades_per_chassis, **kw)

    # -- model hot-swap (the paper's daily retrain) ------------------------
    def hot_swap(self, new_service: PredictionService) -> None:
        """Pack the retrained forest into the standby buffer, then flip
        atomically. Serving calls between pack and flip keep using the
        old model; the queue is untouched, so no arrival is dropped."""
        standby = 1 - self._active
        self._buffers[standby] = pack_service(new_service)
        self._active = standby
        self.swaps += 1
        if self.obs is not None and self.obs.quality is not None:
            # the old model's confusion/calibration/drift say nothing
            # about the one now serving
            self.obs.quality.on_hot_swap()

    # -- telemetry ingestion (label-bootstrap loop) ------------------------
    def observe(self, history: Population, uf_labels: np.ndarray) -> None:
        """Fold freshly labeled telemetry into the subscription
        aggregates (incremental twin of recomputing
        `features.subscription_aggregates` offline)."""
        self.table = ingest_population(self.table, history, uf_labels)

    # -- serving -----------------------------------------------------------
    def submit(self, batch: ArrivalBatch) -> list[ServeResult]:
        """Ingest arrivals through the single queue; serve every full
        micro-batch. Returns the results that became ready (possibly
        empty — call `flush` to drain a partial tail batch). This is
        the 1-host special case of `submit_to` — pipelines configured
        with ``n_ingest_hosts > 1`` must say which host queue an
        arrival belongs to."""
        if self.config.n_ingest_hosts != 1:
            raise ValueError(
                "submit() is the single-queue (1-host) path; with "
                f"n_ingest_hosts={self.config.n_ingest_hosts} use "
                "submit_to(host, batch, t=...)")
        return self.submit_to(0, batch)

    def submit_to(self, host: int, batch: ArrivalBatch,
                  t=None) -> list[ServeResult]:
        """Push a stamped arrival chunk into `host`'s ingest queue and
        serve whatever the fleet watermark releases. `t`: per-arrival
        strictly increasing stamps ((B,) array; None = the host-local
        unit clock). Micro-batches form over the *merged* stream, so
        with several hosts a batch is only served once every host's
        clock has passed it — push (or `flush`) regularly from all
        hosts to keep the watermark moving."""
        with self._push():
            with self._span("ingest"):
                self.ingest.submit_to(host, batch, t)
            with self._span("merge"):
                events = self.ingest.poll()
            return self._drain_events(events)

    def depart_to(self, host: int, servers, cores, p95_eff, is_uf,
                  t=None, mem_gb=None) -> list[ServeResult]:
        """Push a stamped departure batch into `host`'s ingest queue.
        The departure takes effect at its merged-stream position, at
        micro-batch granularity: it is applied before any micro-batch
        served after it, so every arrival merged later sees the freed
        capacity (and, sharded, power tokens) — and so do arrivals
        merged earlier that are still pending in the current unfilled
        micro-batch window (batching trades exact stream position for
        batch efficiency; the order stays deterministic and the watt
        budget is never exceeded either way). Unsharded, the released
        runs are gathered on the host and applied in one dispatch just
        before the next read of the aggregates — the next micro-batch's
        placement, the next cap run, `flush` or a `state` read — which
        keeps this order (`_apply_departures`). Advancing this host's
        clock can release queued micro-batches — any results are
        returned."""
        with self._push():
            with self._span("ingest"):
                self.ingest.depart_to(host, DepartureBatch(
                    np.asarray(servers, np.int32),
                    np.asarray(cores, np.float32),
                    np.asarray(p95_eff, np.float32),
                    np.asarray(is_uf, bool),
                    None if mem_gb is None
                    else np.asarray(mem_gb, np.float32)), t)
            with self._span("merge"):
                events = self.ingest.poll()
            return self._drain_events(events)

    def cap_to(self, host: int, chassis, power_w,
               t=None) -> list[ServeResult]:
        """Push a stamped chassis power-sample batch into `host`'s
        ingest queue — the cap/uncap events of the power-emergency
        plane (`serve.emergency`, third stream-event kind). Samples
        apply at their merged-stream position, so alarms, lifts, and
        the capacity/token effects of any mitigation traffic stay
        deterministic across host counts. Requires the pipeline to be
        built with `emergency_cfg` and/or `adaptive_cfg` (either plane
        consumes the samples). Advancing this host's clock can release
        queued micro-batches — any results are returned."""
        if self.emergency_cfg is None and self.adaptive_cfg is None:
            raise ValueError(
                "cap_to() needs a pipeline built with emergency_cfg "
                "or adaptive_cfg")
        with self._push():
            with self._span("ingest"):
                self.ingest.cap_to(host, CapBatch(
                    np.asarray(chassis, np.int32),
                    np.asarray(power_w, np.float32)), t)
            with self._span("merge"):
                events = self.ingest.poll()
            return self._drain_events(events)

    def flush(self) -> ServeResult | None:
        """Serve everything still queued, watermark ignored (padded up
        to the batch size; chunked if the drain releases more than one
        micro-batch). Returns one concatenated result, or None."""
        with self._push():
            with self._span("merge"):
                events = self.ingest.drain()
            out = self._drain_events(events)
            if self._queued:
                merged = _concat_batches(self._pending)
                self._queue_wait(0, len(merged))
                self._pending, self._queued = [], 0
                self._pending_pushed = []
                out.append(self._serve_padded(merged))
            if self._pending_caps:  # trailing caps with no batch to ride
                with self._span("cap"):
                    self._flush_caps()
            if self._pending_departures:    # trailing departures
                with self._span("depart"):
                    self._flush_departures()
        if not out:
            return None
        return out[0] if len(out) == 1 else _concat_results(out)

    def _drain_events(self, events: MergedEvents) -> list[ServeResult]:
        """Apply one released merged-event window in stream order:
        arrival runs accumulate toward (and serve) full micro-batches,
        departure runs apply at their merged position (before any
        micro-batch served after them — see `depart_to` for the
        batch-granularity caveat)."""
        bs = self.config.batch_size
        out: list[ServeResult] = []
        rec = None if self.obs is None else self.obs.recorder
        pos = 0
        for kind, lo, hi in events.runs():
            run = slice(pos, pos + (hi - lo))
            t_run = events.t[run]
            pos += hi - lo
            if len(t_run):
                # the merged stream is the watermark clock the §17
                # pillars aggregate on
                self._watermark = float(t_run[-1])
            if kind == CAPPING:
                caps = slice_soa(events.caps, lo, hi)
                if rec is not None:
                    rec.record_caps(t_run, caps)
                with self._span("cap"):
                    self._apply_caps(caps, t_run)
                continue
            if kind != ARRIVAL:
                d = slice_soa(events.departures, lo, hi)
                if rec is not None:
                    rec.record_departures(t_run, d)
                with self._span("depart"):
                    self._apply_departures(d.server, d.cores, d.p95_eff,
                                           d.is_uf, d.mem_gb)
                continue
            arr = slice_soa(events.arrivals, lo, hi)
            if rec is not None:
                rec.record_arrivals(t_run, arr)
            self._pending.append(arr)
            if events.pushed is not None:
                self._pending_pushed.append(events.pushed[run])
            self._queued += hi - lo
            if self._queued < bs:
                continue
            merged = _concat_batches(self._pending)  # one copy, slice
            start = 0
            while self._queued - start >= bs:
                self._queue_wait(start, start + bs)
                out.append(self._serve_padded(
                    slice_soa(merged, start, start + bs)))
                start += bs
            self._pending = [slice_soa(merged, start, len(merged))]
            if self._pending_pushed:
                self._pending_pushed = [
                    np.concatenate(self._pending_pushed)[start:]]
            self._queued = self._queued - start
        return out

    def _queue_wait(self, lo: int, hi: int) -> None:
        """Record the ``queue`` wait span of the batch about to be
        served from the pending arrivals [lo, hi): from the push of the
        oldest of them to now (no-op without a tracer)."""
        if self._pending_pushed:
            t0 = float(np.concatenate(self._pending_pushed)[lo:hi].min())
            self._tracer.record_wait("queue", t0, batch=self._batches + 1)

    def serve(self, batch: ArrivalBatch) -> ServeResult:
        """Serve one batch synchronously, bypassing the queue (chunks
        internally if larger than the configured micro-batch). Bypassed
        batches are invisible to the flight recorder — only the
        streamed (queue) path is replayable (`obs.recorder`)."""
        self._recorder_suspended = True
        try:
            bs = self.config.batch_size
            if len(batch) <= bs:
                return self._serve_padded(batch)
            parts = [ArrivalBatch(*(getattr(batch, f)[i:i + bs]
                                    for f in
                                    ArrivalBatch.__dataclass_fields__))
                     for i in range(0, len(batch), bs)]
            return _concat_results([self._serve_padded(p)
                                    for p in parts])
        finally:
            self._recorder_suspended = False

    def _serve_padded(self, batch: ArrivalBatch) -> ServeResult:
        """Serve one micro-batch (padded to the batch size) under the
        next batch sequence number, the batch its spans are given."""
        self._batches += 1
        tr = self._tracer
        if tr is None:
            return self._serve_batch(batch)
        tr.batch = self._batches
        try:
            return self._serve_batch(batch)
        finally:
            tr.batch = -1

    def _serve_batch(self, batch: ArrivalBatch) -> ServeResult:
        b = len(batch)
        pad_to = self.config.batch_size
        packed, meta = self._buffers[self._active]
        with self._span("featurize"):
            x = featurize_batch(self.table, batch, pad_to=pad_to)
        with self._span("infer"):
            q = self._query(packed, meta, x)
            is_uf = q["workload_type_used"] == UF
            policy = self.config.policy
            if policy.use_utilization_predictions:
                p95_eff = bucket_to_p95_jnp(q["p95_bucket_used"])
            else:
                p95_eff = jnp.ones(pad_to, jnp.float32)
        cores = jnp.zeros(pad_to, jnp.float32) \
            .at[:b].set(jnp.asarray(batch.cores))
        mem = jnp.zeros(pad_to, jnp.float32) \
            .at[:b].set(jnp.asarray(batch.memory_gb))
        valid = jnp.arange(pad_to) < b
        if self._pending_departures:
            with self._span("depart"):
                self._flush_departures()
        with self._span("place"):
            servers = self._place(cores, is_uf, p95_eff, valid, mem)
        self.served += b
        with self._span("commit"):
            # the quality pillar also wants the raw (ungated) head
            # outputs + confidences, and a fused sweep its counters —
            # fetched in the same device_get, outputs only, so
            # decisions are untouched either way
            dec = (servers, q["workload_type_used"],
                   q["p95_bucket_used"], p95_eff, q["conservative"])
            raw = None
            if self.obs is not None and self.obs.quality is not None:
                raw = (q["workload_type"], q["workload_conf"],
                       q["p95_bucket"], q["p95_conf"])
            sweep, self._sweep = self._sweep, None
            dec, raw, counters = self._fetch(
                (dec, raw, None if sweep is None else sweep[0]))
        res = ServeResult(*(a[:b] for a in dec))
        with self._span("record"):
            if sweep is not None:
                self._alarms += int(counters.alarms)
                self._record_sweep(counters, windows=sweep[1])
            self._record_batch(
                batch, res,
                raw=None if raw is None else tuple(a[:b] for a in raw))
        return res

    def _query(self, packed, meta, x):
        """Inference stage of one padded micro-batch."""
        return served_query(packed, meta, x, kernel=self._kernel)

    def _place(self, cores, is_uf, p95_eff, valid, mem):
        """Placement stage of one padded micro-batch: run the batched
        Algorithm-1 scan against the cluster state and return the (B,)
        server decisions (FAIL_* codes on reject). Cap sub-windows
        queued since the last batch ride along fused in front of the
        scan (`placement.place_batch_caps`) — the batch plus a full
        emergency sweep is still one compiled dispatch, whose counters
        are fetched with the batch's decisions. The sharded pipeline
        overrides this hook and `_query`."""
        if self._pending_caps:
            n_windows = len(self._pending_caps)
            pw, mask, ts = self._stacked_caps()
            self._pending_caps = []
            if self.obs is not None:
                self.obs.registry.counter(
                    "serve_dispatch_total",
                    help="compiled kernel dispatches, by call site",
                    kind="place_batch_caps").inc()
            (self.state, servers, self._emergency,
             sweep) = placement.place_batch_caps(
                self.state, self._emergency, pw, mask, ts, cores,
                is_uf, p95_eff, valid, self.res_cap,
                self.config.policy, self.cores_per_server,
                self.emergency_cfg, mem_gb=mem)
            self._sweep = (sweep, n_windows)
            return servers
        if self.obs is not None:
            self.obs.registry.counter(
                "serve_dispatch_total",
                help="compiled kernel dispatches, by call site",
                kind="place_batch").inc()
        self.state, servers = placement.place_batch(
            self.state, cores, is_uf, p95_eff, valid, self.res_cap,
            self.config.policy, self.cores_per_server, mem_gb=mem)
        return servers

    def _stacked_caps(self):
        """Densify the queued unique-chassis sub-windows into stacked
        (W, C) `masked_step` operands, merged order preserved."""
        dtype = self.state.free_cores.dtype
        rows = [emergency.scatter_samples(self.n_chassis, c, p, t, np,
                                          np.float64)
                for c, p, t in self._pending_caps]
        pw = jnp.asarray(np.stack([r[0] for r in rows]), dtype)
        mask = jnp.asarray(np.stack([r[1] for r in rows]))
        ts = jnp.asarray(np.stack([r[2] for r in rows]), dtype)
        return pw, mask, ts

    def depart(self, servers, cores, p95_eff, is_uf,
               mem_gb=None) -> None:
        """Release departed VMs' aggregates (batched, order-free) — the
        1-host special case. The rows join the gathered departures and
        are applied before the next read of the aggregates (the next
        micro-batch's placement, cap run or `state` read), so every
        later reader sees them. `depart_to` is the stream-ordered
        per-host path, and like `submit` this refuses multi-host
        pipelines: applying a departure out of merged-stream order
        would silently break the deterministic order the merge
        promises."""
        if self.config.n_ingest_hosts != 1:
            raise ValueError(
                "depart() is the single-queue (1-host) path; with "
                f"n_ingest_hosts={self.config.n_ingest_hosts} use "
                "depart_to(host, ..., t=...)")
        with self._span("depart"):
            self._apply_departures(servers, cores, p95_eff, is_uf, mem_gb)

    def _apply_departures(self, servers, cores, p95_eff, is_uf,
                          mem_gb=None) -> None:
        """Take a departure batch (the merged-stream consumer;
        `ShardedServePipeline` overrides with the per-shard route +
        in-scan pool credit). Queued cap windows flush first: they were
        merged earlier and must read the pre-departure aggregates. The
        batch's live rows (``servers >= 0``; `remove_batch` ignores the
        rest) are then gathered on the host, and `_flush_departures`
        applies every gathered run in one dispatch before the next read
        or write of the aggregates: the next micro-batch's placement,
        the next cap run, a `flush` or any `state` read. So the order
        `depart_to` promises is unchanged; only the number of
        dispatches falls. ``mem_gb=None`` releases zero GB."""
        self._flush_caps()
        servers = np.asarray(servers)
        live = servers >= 0
        if not live.any():
            return
        block = np.zeros((4, int(live.sum())),
                         self._state.free_cores.dtype)
        for row, col in zip(block, (cores, p95_eff, is_uf, mem_gb)):
            if col is not None:
                row[:] = np.asarray(col)[live]
        self._pending_departures.append(
            (servers[live].astype(np.int32), block))

    def _flush_departures(self) -> None:
        """Apply every gathered departure run to the state: one
        `remove_batch` dispatch (a ``remove`` span) per chunk of at most
        `DEPART_LADDER`'s largest size, padded with ignored rows to the
        smallest size that holds it. Rows keep their push order, so on
        a backend that scatters in row order the result equals one
        dispatch per run."""
        parts, self._pending_departures = self._pending_departures, []
        servers = np.concatenate([p[0] for p in parts])
        block = np.concatenate([p[1] for p in parts], axis=1)
        top = DEPART_LADDER[-1]
        for lo in range(0, len(servers), top):
            n = min(top, len(servers) - lo)
            size = next(s for s in DEPART_LADDER if s >= n)
            srv = np.full(size, -1, np.int32)
            srv[:n] = servers[lo:lo + n]
            blk = np.zeros((4, size), block.dtype)
            blk[:, :n] = block[:, lo:lo + n]
            with self._span("remove"):
                self._state = _remove_gathered(self._state, srv, blk)

    def _warm_departures(self) -> None:
        """Compile the gathered removal at every `DEPART_LADDER` size
        with an all-ignored batch (its result is dropped), so no
        departure compiles while serving."""
        dtype = self._state.free_cores.dtype
        for size in DEPART_LADDER:
            _remove_gathered(self._state, np.full(size, -1, np.int32),
                             np.zeros((4, size), dtype))

    # -- power-emergency plane (serve.emergency) ---------------------------
    def _apply_caps(self, batch: CapBatch, t: np.ndarray) -> None:
        """Consume one merged CAPPING run: split it into unique-chassis
        sub-windows and *queue* them in merged order for fusion into
        the next placement dispatch (`_place`). A cap touches only the
        emergency state, and every mutation of the aggregates it reads
        flushes the queue first (departures) or applies it ahead of
        the mutation in the same dispatch (arrival batches), so the
        deferred windows see exactly the aggregates they would have
        seen dispatched standalone at their merged position. Stamps
        are rebased to the first cap stamp this pipeline ever saw: the
        f32 serving path stores the emergency clocks in the state
        dtype, and epoch-second stamps (~1e9) would otherwise quantize
        the 30 s lift/dwell windows away — relative session time keeps
        sub-second resolution for years of stream.

        The adaptive controller (`adaptive_cfg`) consumes the same
        sub-windows *eagerly*: its scan reads only the placement
        aggregates (which every queued-cap consumer already sees
        consistently — mutations flush the queue first) and its
        stepped ratio must be in force for the very next micro-batch,
        so deferring it would lag the budget by one batch."""
        if self.emergency_cfg is None and self.adaptive_cfg is None:
            raise ValueError(
                "received CAPPING events but the pipeline was built "
                "without emergency_cfg or adaptive_cfg")
        # departures merged before this run: the scans and the queued
        # windows read the post-departure aggregates (`cap` holds the
        # dispatch; a `depart` span here would count it twice)
        if self._pending_departures:
            self._flush_departures()
        if self._cap_epoch is None:
            self._cap_epoch = float(t[0])
        t = np.asarray(t, np.float64) - self._cap_epoch
        for lo, hi in _unique_chassis_windows(batch.chassis):
            if self.adaptive_cfg is not None:
                self._adaptive_scan(batch.chassis[lo:hi],
                                    batch.power_w[lo:hi])
            if self.emergency_cfg is not None:
                self._pending_caps.append(
                    (batch.chassis[lo:hi], batch.power_w[lo:hi],
                     t[lo:hi]))
        # the ballooning rung applies its windows eagerly: the fused
        # placement kernels step the emergency state alone, and a
        # deferred balloon would see a stale memory ledger once the
        # batch it rides with mutates `mem_nuf`
        if self._balloon is not None:
            self._flush_caps()

    def _flush_caps(self) -> None:
        """Apply queued cap sub-windows through the standalone kernel —
        the path for windows no placement batch will carry (reads of
        `emergency`/`alarms`, departures, end-of-stream `flush`). Each
        window reads `state`, which applies any gathered departures
        first (none, in merged order: a cap run applies them before it
        queues a window)."""
        pending, self._pending_caps = self._pending_caps, []
        for chassis, power_w, t in pending:
            with self._span("emergency"):
                out, bout = self._cap_window(chassis, power_w, t)
            if self.obs is None:
                self._alarms += int(self._fetch(out.alarm).sum())
                continue
            alarm, cut_w, leftover_w, cbl, bal = self._fetch((
                out.alarm, out.cut_w, out.leftover_w, out.cut_by_level_w,
                None if bout is None else (bout,
                                           self._balloon.ballooned_gb)))
            alarms = int(alarm.sum())
            self._alarms += alarms
            if bal is not None:
                self._record_balloon(*bal)
            self._record_sweep(placement.SweepCounters(
                samples=len(chassis), alarms=alarms,
                cut_w=np.asarray(cut_w, np.float64).sum(),
                leftover_w=np.asarray(leftover_w, np.float64).sum(),
                cut_by_level_w=np.asarray(cbl, np.float64).reshape(
                    -1, emergency.N_LEVELS).sum(0)), windows=1)

    def _cap_window(self, chassis, power_w, t):
        """Apply one unique-chassis sample window (unsharded path) —
        through the balloon-then-cap kernel when the ballooning rung is
        attached, the plain emergency kernel otherwise. Returns the
        emergency and balloon outputs (None without the rung), still
        on the device."""
        dtype = self.state.free_cores.dtype
        pw, mask, ts = emergency.scatter_samples(
            self.n_chassis, chassis, power_w, t, jnp, dtype)
        if self._balloon is not None:
            if self.obs is not None:
                self.obs.registry.counter(
                    "serve_dispatch_total",
                    help="compiled kernel dispatches, by call site",
                    kind="balloon_cap_step").inc()
            fn = _balloon_cap_step_fn(self.emergency_cfg,
                                      self.config.planes.ballooning)
            (self._emergency, self._balloon, out,
             bout) = fn(self.state.gamma_nuf, self.state.gamma_uf,
                        self.state.chassis_servers, self.state.mem_nuf,
                        self._emergency, self._balloon, pw, mask, ts)
            return out, bout
        if self.obs is not None:
            self.obs.registry.counter(
                "serve_dispatch_total",
                help="compiled kernel dispatches, by call site",
                kind="cap_step").inc()
        fn = _cap_step_fn(self.emergency_cfg)
        self._emergency, out = fn(self.state.gamma_nuf,
                                  self.state.gamma_uf,
                                  self.state.chassis_servers,
                                  self._emergency, pw, mask, ts)
        return out, None

    # -- ballooning rung (serve.ballooning) --------------------------------
    @property
    def balloon_state(self):
        """Current `serve.ballooning.BalloonState` (None with the rung
        off). Reading it flushes queued cap windows like `emergency`
        (with ballooning on they are applied eagerly anyway)."""
        self._flush_caps()
        return self._balloon

    def ballooned_gb(self) -> float:
        """Fleet-wide GB currently ballooned out (0.0 with the rung
        off)."""
        if self._balloon is None:
            return 0.0
        self._flush_caps()
        return ballooning.total_ballooned_gb(self._balloon)

    def _record_balloon(self, bout, ballooned_gb) -> None:
        """Export one balloon sweep's outputs (fetched to the host):
        reclaim/release/absorb counters and the standing-balloon gauge
        (`ballooned_gb`, the state's per-chassis balloons after it)."""
        reg = self.obs.registry
        reg.counter("balloon_reclaimed_gb_total",
                    help="GB ballooned out of NUF VMs").inc(
                        float(np.asarray(bout.reclaimed_gb,
                                         np.float64).sum()))
        reg.counter("balloon_released_gb_total",
                    help="ballooned GB handed back on alarm clear").inc(
                        float(np.asarray(bout.released_gb,
                                         np.float64).sum()))
        reg.counter("balloon_absorbed_watts_total",
                    help="DRAM watts absorbed by standing + fresh "
                    "balloons").inc(
                        float(np.asarray(bout.absorbed_w,
                                         np.float64).sum()))
        reg.counter("balloon_inflations_total",
                    help="chassis sweeps where the rung fired").inc(
                        int(np.asarray(bout.inflated).sum()))
        reg.gauge("balloon_ballooned_gb",
                  help="fleet GB currently ballooned out").set(
                      float(np.asarray(ballooned_gb).sum()))

    def throttled_by_level(self) -> np.ndarray:
        """(L,) cumulative throttled-seconds per criticality level
        (index `emergency.CRIT_UF` = critical) — the Table-4-style
        impact counter the emergency plane maintains."""
        if self.emergency is None:
            return np.zeros(emergency.N_LEVELS)
        return emergency.throttled_by_level(self.emergency)

    def mitigation_due_chassis(self) -> np.ndarray:
        """Global ids of chassis whose cap has dwelled past
        `emergency_cfg.dwell_s` with the critical level throttled —
        feed these (with a VM registry) to
        `serve.mitigation.plan_migrations` and push the plan's paired
        events through `depart_to`."""
        if self.emergency is None:
            return np.empty(0, np.int64)
        due = np.asarray(emergency.mitigation_due(self.emergency_cfg,
                                                  self.emergency))
        return np.flatnonzero(due.reshape(-1))

    def reset_dwell(self, chassis) -> None:
        """Zero the dwell clock of the given global chassis ids (call
        after emitting a migration plan for them)."""
        mask = np.zeros(self.n_chassis, bool)
        mask[np.asarray(chassis, np.int64)] = True
        self.emergency = emergency.reset_dwell(
            self.emergency, jnp.asarray(self._dwell_mask(mask)), jnp)

    def _dwell_mask(self, mask: np.ndarray) -> np.ndarray:
        """Reshape a (C,) global chassis mask to the emergency state's
        chassis layout (identity unsharded)."""
        return mask

    # -- diagnostics -------------------------------------------------------
    def chassis_headroom_w(self, budget_w) -> np.ndarray:
        """(C,) watts of remaining per-chassis admission headroom."""
        return admission.headroom_w(self.state, budget_w,
                                    self.blades_per_chassis,
                                    self.power_model)


@dataclass(frozen=True)
class ShardedServeConfig(ServeConfig):
    """`ServeConfig` plus the sharded-placement knobs (docs/sharding.md
    discusses picking them). `batch_size` must be divisible by
    `n_shards`; `use_shard_map='auto'` maps shards onto mesh devices
    when the runtime has enough and falls back to the single-device
    vmap twin otherwise."""
    n_shards: int = 1
    use_shard_map: bool | str = "auto"      # True | False | 'auto'
    spill_rounds: int | None = None         # default: n_shards - 1
    rebalance_tokens: bool = True
    shard_table: bool = True                # partition SubscriptionTable


class ShardedServePipeline(ServePipeline):
    """`ServePipeline` with the cluster state partitioned across a
    ``("shard",)`` device mesh (`serve.sharding`, DESIGN.md §10).

    Featurization is shard-agnostic (one batched call; the subscription
    table is row-partitioned over the mesh when `shard_table` is set).
    On a mesh, forest inference splits the batch over the devices
    (`sharding.query_sharded`: a Pallas kernel is never partitioned
    automatically). The placement stage fans out: arrivals
    are routed to their home shard, placed concurrently under the
    reserve/commit token protocol, and spilled cross-shard when the
    home shard rejects them. `cluster_budget_w` sets the global watt
    budget the token pools enforce — the sum of admitted `p95*cores`
    across all shards can never exceed its rho-unit conversion, no
    matter how the shards race."""

    def __init__(self, service, table, state, cores_per_server,
                 config: ShardedServeConfig | None = None,
                 cluster_budget_w=_UNSET, **kw):
        config = config or ShardedServeConfig()
        if config.batch_size % config.n_shards:
            raise ValueError(
                f"batch_size {config.batch_size} not divisible by "
                f"n_shards {config.n_shards}")
        config = replace(config, planes=_legacy_planes(
            config.planes, type(self).__name__,
            cluster_budget_w=cluster_budget_w))
        super().__init__(service, table, state, cores_per_server,
                         config=config, **kw)
        config = self.config        # planes merged by the superclass
        if config.use_shard_map == "auto":
            self.mesh = sharding.shard_mesh(config.n_shards) \
                if config.n_shards > 1 else None
        elif config.use_shard_map:
            self.mesh = sharding.shard_mesh(config.n_shards)
            if self.mesh is None:
                raise RuntimeError(
                    f"use_shard_map=True needs >= {config.n_shards} "
                    f"devices, have {len(jax.devices())}")
        else:
            self.mesh = None
        budget = config.planes.cluster_budget
        self.cluster_budget_w = None if budget is None else budget.watts
        # gross = the ratio-1.0 (R,) token allowance; the adaptive
        # controller retargets free pools against it (`retarget_pool`)
        gross = np.full(N_RESOURCES, np.inf) if budget is None else \
            sharding.resource_pool_from_budget(
                budget, state.n_servers, self.power_model)
        finite = np.isfinite(gross)
        self._has_pool = bool(finite.any())
        if self._has_pool:
            # a warm-started cluster has resources already committed;
            # the pool is the *remaining* allowance per axis, so the
            # budget invariant holds from the first batch (the sim
            # backend nets identically)
            committed = np.asarray(state.res_peak, np.float64).sum(0)
            pool_total = np.where(
                finite, np.maximum(gross - committed, 0.0), np.inf)
        else:
            pool_total = None
        self.sharded = sharding.shard_state(
            self.state, config.n_shards, rho_cap=self.res_cap,
            pool_total=pool_total)
        if self.mesh is not None:
            self.sharded = sharding.device_put_sharded_state(
                self.sharded, self.mesh)
            if config.shard_table:
                self.table = shard_table(self.table, self.mesh)
        self.state = None        # self.sharded is the source of truth
        self._sharded_cap_base = self.sharded.res_cap
        self._pool_base = None if not self._has_pool else \
            jnp.asarray(np.broadcast_to(
                gross / config.n_shards,
                (config.n_shards, N_RESOURCES)),
                self.sharded.pool.dtype)
        self._ratio_prev = np.ones(config.n_shards)
        self.spill_info = {"rounds": 0, "spilled": 0,
                           "spill_admitted": 0}

    def _warm_departures(self) -> None:
        """Nothing to compile: departures go straight to the shards
        (`_apply_departures`), so the gathered buffer stays empty."""

    def _query(self, packed, meta, x):
        if self.mesh is None:
            return super()._query(packed, meta, x)
        return sharding.query_sharded(packed, meta, x, self._kernel,
                                      self.mesh)

    # -- sharded placement stage -------------------------------------------
    def _place(self, cores, is_uf, p95_eff, valid, mem):
        cfg = self.config
        kw = {}
        fused = bool(self._pending_caps)
        if fused:
            n_windows = len(self._pending_caps)
            kw = dict(emer=self._emergency, caps=self._sharded_caps(),
                      ecfg=self.emergency_cfg)
            self._pending_caps = []
        if self.obs is not None:
            kw["registry"] = self.obs.registry
        out = sharding.place_group_sharded(
            self.sharded, np.asarray(cores), np.asarray(is_uf),
            np.asarray(p95_eff), np.asarray(valid), cfg.policy,
            self.cores_per_server, mem_gb=np.asarray(mem),
            mesh=self.mesh, spill_rounds=cfg.spill_rounds,
            rebalance=cfg.rebalance_tokens, **kw)
        if fused:
            (self.sharded, servers, info, self._emergency,
             sweep) = out
            self._alarms += int(np.asarray(sweep.alarms))
            self._record_sweep(sweep, windows=n_windows)
        else:
            self.sharded, servers, info = out
        self.spill_info = {k: self.spill_info[k] + info[k]
                           for k in self.spill_info}
        self._record_spill(info)
        return servers.astype(np.int32)

    def _record_spill(self, info: dict) -> None:
        """Fold one sharded placement call's spillover/token counters
        into the registry (host-side, from the already-returned
        ``info`` dict)."""
        if self.obs is None:
            return
        reg = self.obs.registry
        reg.counter("serve_spill_rounds_total",
                    help="spillover rounds run beyond the home round"
                    ).inc(max(info["rounds"] - 1, 0))
        reg.counter("serve_spilled_total",
                    help="arrivals that entered a spillover round").inc(
                        info["spilled"])
        reg.counter("serve_spill_admits_total",
                    help="arrivals admitted by a spillover round").inc(
                        info["spill_admitted"])
        if self._has_pool:
            reg.counter("serve_tokens_drawn_total",
                        help="power tokens drawn from the pools, "
                        "rho units").inc(
                            max(0.0, info.get("tokens_drawn", 0.0)))
            drawn = np.asarray(info.get(
                "tokens_drawn_vec", np.zeros(N_RESOURCES)), np.float64)
            pools = np.asarray(self.sharded.pool)
            for r, name in enumerate(RESOURCES):
                reg.counter("serve_tokens_drawn_res_total",
                            help="tokens drawn from the pools, by "
                            "resource axis",
                            resource=name).inc(max(0.0, float(drawn[r])))
            for i, row in enumerate(pools):
                reg.gauge("serve_pool_tokens",
                          help="remaining power tokens, by shard",
                          shard=str(i)).set(float(row[0]))
                for r, name in enumerate(RESOURCES):
                    if np.isfinite(row[r]):
                        reg.gauge("serve_pool_resources",
                                  help="remaining tokens, by shard "
                                  "and resource axis",
                                  shard=str(i),
                                  resource=name).set(float(row[r]))

    def _pool_tokens_left(self) -> float:
        if not self._has_pool:
            return float("inf")
        return float(np.asarray(self.sharded.pool)[:, 0].sum())

    def _sharded_caps(self):
        """Densify queued sub-windows into the stacked (N, W, C/N)
        per-shard operands of the fused home-round kernel."""
        dtype = self.sharded.shards.free_cores.dtype
        rows = [sharding.split_caps(self.sharded, c, p, t)
                for c, p, t in self._pending_caps]
        pw = jnp.asarray(np.stack([r[0] for r in rows], axis=1), dtype)
        mask = jnp.asarray(np.stack([r[1] for r in rows], axis=1))
        ts = jnp.asarray(np.stack([r[2] for r in rows], axis=1), dtype)
        return pw, mask, ts

    def _apply_departures(self, servers, cores, p95_eff, is_uf,
                          mem_gb=None) -> None:
        """Route each departure to its owner shard (per-shard
        batches, `sharding.split_departures`) and credit the freed
        (R,) demand vector back to that shard's pool in the consuming
        scan (`sharding.consume_departures`). Queued cap windows flush
        first — they read pre-departure aggregates."""
        self._flush_caps()
        if self.obs is not None and self._has_pool:
            srv = np.asarray(servers)
            live = srv >= 0
            credit = (np.asarray(p95_eff, np.float64)[live]
                      * np.asarray(cores, np.float64)[live]).sum()
            self.obs.registry.counter(
                "serve_tokens_credited_total",
                help="power tokens credited back by departures, "
                "rho units").inc(float(credit))
        self.sharded = sharding.remove_sharded(
            self.sharded, servers, cores, p95_eff, is_uf,
            mem_gb=mem_gb)

    # -- sharded adaptive oversubscription ---------------------------------
    def _init_adaptive(self):
        """Controller state partitioned like the cluster (leading
        shard axis over the same contiguous chassis blocks)."""
        return sharding.init_adaptive_sharded(
            self.adaptive_cfg, self.n_chassis, self.config.n_shards,
            dtype=self.state.free_cores.dtype)

    @property
    def adaptive_ratio(self):
        """(N,) per-shard oversubscription ratios (all 1.0 with the
        controller off) — each shard adapts the slice of the watt
        budget it owns."""
        if self._adaptive is None:
            return np.ones(self.config.n_shards)
        return np.asarray(self._adaptive.ratio)

    def _adaptive_scan(self, chassis, power_w) -> None:
        """Route one unique-chassis sample window to the owner shards
        and step every shard's controller concurrently."""
        if self.obs is not None:
            self.obs.registry.counter(
                "serve_dispatch_total",
                help="compiled kernel dispatches, by call site",
                kind="adaptive_sharded").inc()
        self._adaptive, out = sharding.apply_adaptive_sharded(
            self.adaptive_cfg, self.sharded, self._adaptive, chassis,
            power_w, mesh=self.mesh)
        self._apply_ratio(out)

    def _axis_mult(self, dtype) -> jnp.ndarray:
        """(N, R) per-shard effective ceiling/pool multipliers: each
        shard's adaptive ratio on the watts axis, the shared diurnal
        conditioning on cores/GB (see the unsharded `_axis_mult`)."""
        n = self.config.n_shards
        ones = jnp.ones((n,), dtype)
        r = ones if self._ratio_dev is None \
            else jnp.asarray(self._ratio_dev, dtype)
        return jnp.stack([r, ones, ones], axis=-1) \
            * jnp.asarray(self._res_ratios, dtype)[None]

    def _refresh_caps(self) -> None:
        """Put the current per-axis multipliers in force: rescale each
        shard's slice of the admission ceiling and retarget its free
        token pool against the committed (R,) ledger — never revoking
        tokens already committed to placed VMs
        (`adaptive.retarget_pool` floors the free pool at zero per
        axis), so the reserve/commit conservation invariant survives
        any mint/retire/ratchet sequence."""
        mult = self._axis_mult(self._sharded_cap_base.dtype)
        cap = self._sharded_cap_base * mult[:, None, :]
        pool = self.sharded.pool
        if self._pool_base is not None:
            sh = self.sharded.shards
            # per-axis chassis reduction, watts axis summed exactly as
            # the scalar-era code did (bit-stable against it)
            committed = jnp.stack(
                [jnp.sum(sh.res_peak[..., r], axis=-1)
                 for r in range(N_RESOURCES)], axis=-1)
            pool = adaptive.retarget_pool(
                self.adaptive_cfg, self._pool_base, mult, committed,
                jnp)
        self.sharded = self.sharded._replace(res_cap=cap, pool=pool)

    def _record_adaptive(self, out) -> None:
        """Per-shard export of one controller decision (shard-labelled
        gauge, summed step counters, one reason row per shard)."""
        if self.obs is None:
            return
        reg = self.obs.registry
        ratios = np.asarray(out.ratio)
        ratchets = np.asarray(out.ratchet)
        backoffs = np.asarray(out.backoff)
        for i, r in enumerate(ratios):
            reg.gauge("adaptive_ratio",
                      help="oversubscription ratio of the adaptive "
                      "controller", shard=str(i)).set(float(r))
        reg.counter("adaptive_ratchet_total",
                    help="adaptive-controller up-steps taken").inc(
                        int(ratchets.sum()))
        reg.counter("adaptive_backoff_total",
                    help="adaptive-controller down-steps taken").inc(
                        int(backoffs.sum()))
        if self.obs.adaptive is not None:
            now = time.time()
            n_known = np.asarray(out.n_known)
            n_stable = np.asarray(out.n_stable)
            frac = np.asarray(out.stable_frac)
            hot = np.asarray(out.hot)
            for i in range(len(ratios)):
                self.obs.adaptive.record(
                    t=now, shard=i, ratio=float(ratios[i]),
                    stable_frac=float(frac[i]),
                    n_known=int(n_known[i]),
                    n_stable=int(n_stable[i]),
                    action=1 if ratchets[i] else
                    (-1 if backoffs[i] else 0),
                    reason=adaptive.decision_reason(
                        float(self._ratio_prev[i]), float(ratios[i]),
                        int(n_known[i]), bool(ratchets[i]),
                        bool(backoffs[i]), bool(hot[i])))
        self._ratio_prev = ratios

    # -- sharded power-emergency plane -------------------------------------
    def _init_emergency(self):
        """Emergency state partitioned like the cluster (leading shard
        axis over the same contiguous chassis blocks)."""
        return sharding.init_emergency_sharded(
            self.n_chassis, self.config.n_shards,
            dtype=self.state.free_cores.dtype)

    def _init_ballooning(self):
        """Balloon state partitioned like the cluster (leading shard
        axis over the same contiguous chassis blocks)."""
        return sharding.init_ballooning_sharded(
            self.n_chassis, self.config.n_shards,
            dtype=self.state.free_cores.dtype)

    def _cap_window(self, chassis, power_w, t):
        """Apply one unique-chassis sample window: route samples to
        their owner shards and run every shard's alarm + apportionment
        kernel concurrently (vmap, or shard_map on the mesh) — with
        the ballooning rung in front when attached. Returns the
        emergency and balloon outputs (None without the rung)."""
        if self._balloon is not None:
            if self.obs is not None:
                self.obs.registry.counter(
                    "serve_dispatch_total",
                    help="compiled kernel dispatches, by call site",
                    kind="balloon_caps_sharded").inc()
            (self._emergency, self._balloon, out,
             bout) = sharding.apply_caps_ballooned_sharded(
                self.emergency_cfg, self.config.planes.ballooning,
                self.sharded, self._emergency, self._balloon, chassis,
                power_w, t, mesh=self.mesh)
            return out, bout
        if self.obs is not None:
            self.obs.registry.counter(
                "serve_dispatch_total",
                help="compiled kernel dispatches, by call site",
                kind="caps_sharded").inc()
        self._emergency, out = sharding.apply_caps_sharded(
            self.emergency_cfg, self.sharded, self._emergency, chassis,
            power_w, t, mesh=self.mesh)
        return out, None

    def _dwell_mask(self, mask: np.ndarray) -> np.ndarray:
        return mask.reshape(self.config.n_shards, -1)

    # -- diagnostics -------------------------------------------------------
    def global_state(self) -> placement.DeviceClusterState:
        """Reassembled single-cluster view of the sharded aggregates."""
        return sharding.unshard_state(self.sharded)

    def chassis_headroom_w(self, budget_w) -> np.ndarray:
        return admission.headroom_w(self.global_state(), budget_w,
                                    self.blades_per_chassis,
                                    self.power_model)

    def pool_left(self) -> np.ndarray:
        """(N,) remaining power tokens per shard (rho units) — the
        watts axis of `pool_left_vec`."""
        return np.asarray(self.sharded.pool)[:, 0]

    def pool_left_vec(self) -> np.ndarray:
        """(N, R) remaining tokens per shard and resource axis (+inf
        on unbudgeted axes)."""
        return np.asarray(self.sharded.pool)
