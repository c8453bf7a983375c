"""The one traffic generator: VM populations and stamped serve streams
from the paper's Table I distributions, vectorised.

Every mix under `bench/traffic/*.json` is a set of parameters this
module reads. The arithmetic is a column-at-a-time copy of the
Table I tables of `sim/telemetry.py` (core sizes, memory per core,
lifetimes, deployment sizes), so a stream of 10^5 VMs is drawn in one
numpy pass and no VM is a Python object. Utilizations are whole
percents (what telemetry reports), so every aggregate a subscription
table sums stays exact in float32.
"""
from __future__ import annotations

from dataclasses import dataclass, fields

import numpy as np

#: Paper Table I (copied from the program's `sim/telemetry.py`).
CORE_SIZES = np.array([1, 2, 4, 8, 16, 24, 32])
CORE_PROBS = np.array([0.33, 0.27, 0.21, 0.10, 0.05, 0.03, 0.01])
LIFETIME_BUCKETS = np.array([(1, 1), (2, 2), (3, 5), (6, 10), (10, 25),
                             (26, 720), (721, 2160)])
LIFETIME_PROBS = np.array([0.52, 0.05, 0.10, 0.09, 0.07, 0.08, 0.09])
DEPLOY_SIZE_BUCKETS = np.array([(1, 1), (2, 2), (3, 5), (6, 10), (11, 15),
                                (16, 25), (26, 60)])
DEPLOY_SIZE_PROBS = np.array([0.39, 0.14, 0.16, 0.09, 0.08, 0.05, 0.09])
MEM_PER_CORE_GB = np.array([2, 4, 8])
MAX_LIFETIME_H = float(LIFETIME_BUCKETS[-1, 1])
#: Table I means: cores per VM, lifetime in hours
MEAN_CORES = float((CORE_SIZES * CORE_PROBS).sum())
MEAN_LIFETIME_H = float((LIFETIME_PROBS * LIFETIME_BUCKETS.mean(1)).sum())
#: VM type indices: 0-2 user-facing (web, db, api), 3-6 batch
#: (batch, dev, ci, agent), as `sim.telemetry.VM_TYPES` orders them.
N_UF_TYPES, N_VM_TYPES = 3, 7
UF_FRACTION = 0.45


def sample_bucket(rng, buckets, probs, n: int) -> np.ndarray:
    """(n,) integers: a bucket by `probs`, then uniform within it."""
    i = rng.choice(len(buckets), size=n, p=probs)
    return rng.integers(buckets[i, 0], buckets[i, 1] + 1)


@dataclass
class Subscriptions:
    """Per-subscription criticality propensity and utilization scale."""
    uf_propensity: np.ndarray
    util_scale: np.ndarray

    @classmethod
    def draw(cls, rng, n: int) -> "Subscriptions":
        prop = rng.beta(0.35, 0.35, n)
        prop = np.clip(UF_FRACTION * prop / prop.mean(), 0.02, 0.98)
        return cls(prop, 0.10 + 1.15 * rng.beta(0.40, 0.40, n))


@dataclass
class VMs:
    """Struct of arrays of VMs (ground truth included)."""
    subscription: np.ndarray     # int32
    user_facing: np.ndarray      # bool
    vm_type: np.ndarray          # int32
    cores: np.ndarray            # float32, whole cores
    memory_gb: np.ndarray        # float32
    lifetime_h: np.ndarray       # float32, whole hours
    p95_util: np.ndarray         # float32, whole percent
    avg_util: np.ndarray         # float32, whole percent

    def __len__(self) -> int:
        return len(self.subscription)


def draw_vms(rng, subs: Subscriptions, sub_of_vm: np.ndarray) -> VMs:
    """Draw one VM per entry of `sub_of_vm` (its subscription)."""
    n = len(sub_of_vm)
    uf = rng.random(n) < subs.uf_propensity[sub_of_vm]
    vm_type = np.where(uf, rng.integers(0, N_UF_TYPES, n),
                       N_UF_TYPES + rng.integers(0, N_VM_TYPES - N_UF_TYPES,
                                                 n))
    cores = rng.choice(CORE_SIZES, size=n, p=CORE_PROBS)
    mem = cores * rng.choice(MEM_PER_CORE_GB, size=n)
    life = sample_bucket(rng, LIFETIME_BUCKETS, LIFETIME_PROBS, n)
    # interactive: a diurnal hump (peak over floor); batch: a level
    peak, floor = rng.uniform(35, 90, n), rng.uniform(2, 15, n)
    level = rng.uniform(5, 95, n)
    p95 = np.where(uf, floor + 0.95 * peak, level * rng.uniform(1.0, 1.3, n))
    avg = np.where(uf, floor + 0.30 * peak, level * rng.uniform(0.5, 1.0, n))
    amp = subs.util_scale[sub_of_vm] * rng.uniform(0.88, 1.12, n)
    p95 = np.clip(np.rint(p95 * amp), 1, 100)
    avg = np.minimum(np.clip(np.rint(avg * amp), 0, 100), p95)
    return VMs(sub_of_vm.astype(np.int32), uf, vm_type.astype(np.int32),
               cores.astype(np.float32), mem.astype(np.float32),
               life.astype(np.float32), p95.astype(np.float32),
               avg.astype(np.float32))


def history(rng, n_vms: int) -> tuple[Subscriptions, VMs]:
    """The labelled history a daily retrain hands the serving job:
    `n_vms` VMs over ``max(8, n_vms // 24)`` subscriptions."""
    subs = Subscriptions.draw(rng, max(8, n_vms // 24))
    return subs, draw_vms(rng, subs, rng.integers(0, len(subs.uf_propensity),
                                                  n_vms))


@dataclass
class Stream:
    """A stamped serve stream of deployments (stream seconds).

    Deployment d holds VMs ``[start[d], start[d+1])``, all of one
    subscription, pushed whole by ingest host ``host[d]``; VM i carries
    the strictly increasing stamp ``t[i]``."""
    vms: VMs
    t: np.ndarray                # (n_vms,) float64
    start: np.ndarray            # (n_deploy + 1,) int64
    host: np.ndarray             # (n_deploy,) int32
    depart_h: np.ndarray         # (n_vms,) hours it lives past its stamp

    @property
    def n_deploy(self) -> int:
        return len(self.host)


#: stamps of the VMs of one deployment are this far apart (seconds)
VM_STAMP_STEP = 1e-6


def serve_stream(rng, subs: Subscriptions, n_vms: int, vm_rate_per_s: float,
                 n_hosts: int) -> Stream:
    """At least `n_vms` VMs in deployments arriving as a Poisson process
    whose VM rate is `vm_rate_per_s` (deployments come at that rate over
    the mean deployment size). Each deployment draws its subscription
    from the history's and its host uniformly."""
    mean_size = float((DEPLOY_SIZE_PROBS * DEPLOY_SIZE_BUCKETS.mean(1)).sum())
    n_dep = int(n_vms / mean_size * 1.2) + 16
    while True:
        size = sample_bucket(rng, DEPLOY_SIZE_BUCKETS, DEPLOY_SIZE_PROBS,
                             n_dep)
        if size.sum() >= n_vms:
            break
        n_dep *= 2
    start = np.concatenate([[0], np.cumsum(size)]).astype(np.int64)
    sub = rng.integers(0, len(subs.uf_propensity), n_dep)
    vms = draw_vms(rng, subs, np.repeat(sub, size))
    gaps = rng.exponential(mean_size / vm_rate_per_s, n_dep)
    # a deployment starts after the previous one's last VM stamp
    gaps = np.maximum(gaps, (np.concatenate([[0], size[:-1]]) + 1)
                      * VM_STAMP_STEP)
    t_dep = np.cumsum(gaps)
    within = np.arange(start[-1]) - np.repeat(start[:-1], size)
    t = np.repeat(t_dep, size) + within * VM_STAMP_STEP
    host = rng.integers(0, n_hosts, n_dep).astype(np.int32)
    return Stream(vms, t, start, host, vms.lifetime_h.astype(np.float64))


def residual_lifetimes_h(rng, n: int) -> np.ndarray:
    """(n,) hours left to live for VMs a stationary cluster holds: a VM
    is present in proportion to its Table I lifetime, and has lived a
    uniform share of it."""
    hours = np.arange(1, int(MAX_LIFETIME_H) + 1)
    w = np.zeros(len(hours))
    for (a, b), p in zip(LIFETIME_BUCKETS, LIFETIME_PROBS):
        w[a - 1:b] += p / (b - a + 1)
    w *= hours
    return rng.choice(hours, size=n, p=w / w.sum()) * rng.random(n)


def stationary_stream(rng, subs: Subscriptions, n_fill: int, fill_s: float,
                      n_vms: int, vm_rate_per_s: float,
                      n_hosts: int) -> Stream:
    """The VMs a stationary cluster holds (at least `n_fill`, in whole
    deployments stamped within about `fill_s` stream seconds, each
    leaving after its residual lifetime), then `serve_stream` of
    `n_vms` VMs from the moment the fill ends."""
    fill = serve_stream(rng, subs, n_fill, n_fill / fill_s, n_hosts)
    k = int(np.searchsorted(fill.start[:-1], n_fill))
    n = int(fill.start[k])
    main = serve_stream(rng, subs, n_vms, vm_rate_per_s, n_hosts)
    t0 = max(fill_s, fill.t[n - 1] + VM_STAMP_STEP)
    vms = VMs(*(np.concatenate([getattr(fill.vms, f.name)[:n],
                                getattr(main.vms, f.name)])
                for f in fields(VMs)))
    return Stream(vms, np.concatenate([fill.t[:n], t0 + main.t]),
                  np.concatenate([fill.start[:k], n + main.start]),
                  np.concatenate([fill.host[:k], main.host]),
                  np.concatenate([residual_lifetimes_h(rng, n),
                                  main.depart_h]))


def power_samples(rng, n_chassis: int, budget_w: float, band) -> np.ndarray:
    """One power sample per chassis, uniform in ``band`` times the
    chassis budget (so some chassis alarm and some clear)."""
    lo, hi = band
    return (budget_w * rng.uniform(lo, hi, n_chassis)).astype(np.float32)


def uf_load_traces(seed: int, n_steps: int, loads) -> np.ndarray:
    """(n_steps, len(loads)) interactive load traces of one chassis from
    its seed, drawn VM after VM as `sim.fleet.build_uf_traces` does
    (the fleet engine builds them itself; the reference redraws them
    here)."""
    rng = np.random.default_rng(seed)
    out = np.zeros((n_steps, len(loads)), np.float32)
    wave = 0.12 * np.sin(np.linspace(0, 6 * np.pi, n_steps))
    slow = 0.06 * np.sin(np.linspace(0, 1.5 * np.pi, n_steps))
    for v, base in enumerate(loads):
        noise = rng.normal(0, 0.03, n_steps)
        out[:, v] = np.clip(base + wave + slow + noise, 0.05, 1.2)
    return out
