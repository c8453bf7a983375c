"""Sharded multi-host serve placement (DESIGN.md §10, docs/sharding.md).

One `ServePipeline` serves one cluster from one host; this module is
the fleet-scale path: the chassis state is partitioned over a device
mesh and arrival micro-batches are placed by all shards concurrently
under a consistent-placement protocol.

Layout
    Chassis are assigned to shards in contiguous equal blocks
    (`chassis_to_shard`); servers follow their chassis. Each shard owns
    a disjoint `DeviceClusterState` slice (local server/chassis ids,
    stacked with a leading shard axis — `ShardedState`), so no two
    shards can ever double-book a chassis: only the owner mutates it.

Routing
    Arrivals are dealt round-robin by arrival index (`route_shard` —
    arrival i's home shard is ``i % n_shards``), which keeps per-shard
    batches equal-sized and makes the whole protocol a deterministic
    function of the batch. With one shard the routing is the identity
    and the protocol degenerates to exactly `place_batch` — the
    decision-identity the equivalence tests assert.

Reserve/commit with power-headroom tokens
    A global watt budget converts to a pool of rho-unit tokens
    (`rho_pool_from_budget`) split across shards. Phase 1 (reserve):
    every shard runs the placement scan against its local state,
    drawing tokens from its own pool (`place_batch_pooled`); because
    chassis ownership is exclusive and pools are disjoint, local
    reservations commit immediately and the global budget cannot be
    exceeded, whatever the shards do concurrently. Phase 2 (spillover
    commit): arrivals their home shard rejected are re-offered to the
    other shards in deterministic rounds — round r sends arrival i to
    shard ``(i + r) % n_shards`` — after an all-gather of the shards'
    leftover tokens (the only cross-shard communication; optionally
    rebalanced equally). Token totals are conserved by rebalancing and
    by departures crediting their shard's pool, so the invariant
    ``sum(rho_peak) <= pool_total`` holds for the life of the cluster.

Execution
    Per-shard scans run under `jax.vmap` (single device — the
    semantics oracle) or `jax.shard_map` over a 1-D ``("shard",)``
    mesh (one scan per device — the scaling path benchmarked by
    `benchmarks/serve_sharded.py` with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``). Both
    execute identical per-shard arithmetic and are asserted equal in
    `tests/test_serve_sharded.py`.

Observability (DESIGN.md §14, §17)
    Every sharded scan carries its counters (admits, fails, spills,
    token draws, sweep totals) as extra *outputs* — never inputs — so
    the sharded pipeline feeds the registry, windowed aggregates,
    prediction scorecard, SLO monitor, and flight recorder entirely
    host-side; instrumented and uninstrumented runs stay
    decision-bit-identical (asserted in `tests/test_obs.py` and
    `tests/test_obs_quality.py`).
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.placement import SchedulerPolicy
from repro.core.power_model import F_MAX, ServerPowerModel, idle_power
from repro.core.resources import (N_RESOURCES, ResourceVector,
                                  lift_caps, lift_pool)
from repro.core.rounding import div
from repro.serve import ballooning, emergency
from repro.serve.inference import PackedService, ServiceMeta, served_query
from repro.serve.placement import (DeviceClusterState, FAIL_CAPACITY,
                                   SweepCounters, _apply_cap_windows,
                                   _place_batch_impl, remove_batch)

#: Mesh axis name the serve shards map over.
SHARD_AXIS = "shard"


class ShardedState(NamedTuple):
    """Cluster state partitioned into N disjoint shard slices.

    Every `shards` leaf carries a leading (N,) shard axis over *local*
    server/chassis ids; the `global_*` tables translate local winners
    back to cluster ids and `shard_of_server`/`local_of_server` invert
    them for departures. `res_cap` / `pool` are per-resource (R =
    (watts, cores, GB), `core.resources`): `pool` is each shard's
    remaining token balance per axis (+inf on unbudgeted axes — a
    power-only budget reproduces the scalar watt protocol exactly;
    axis 0 is rho units)."""
    shards: DeviceClusterState      # leaves (N, S/N) / (N, C/N) / ...
    global_server: jnp.ndarray      # (N, S/N) i32 — local -> global id
    global_chassis: jnp.ndarray     # (N, C/N) i32
    shard_of_server: jnp.ndarray    # (S,) i32 — global server -> shard
    local_of_server: jnp.ndarray    # (S,) i32 — global server -> local id
    res_cap: jnp.ndarray            # (N, C/N, R) — chassis admission caps
    pool: jnp.ndarray               # (N, R) — tokens left per resource

    @property
    def n_shards(self) -> int:
        return self.global_server.shape[0]

    @property
    def n_servers(self) -> int:
        return self.shard_of_server.shape[0]


def chassis_to_shard(n_chassis: int, n_shards: int) -> np.ndarray:
    """(C,) shard owner of each chassis: contiguous equal blocks.

    Shard counts must divide the chassis count (docs/sharding.md
    discusses picking them); contiguity keeps a rack's chassis on one
    shard under the standard ``chassis = server // blades`` layout."""
    if n_chassis % n_shards:
        raise ValueError(
            f"n_shards={n_shards} must divide n_chassis={n_chassis}")
    return np.repeat(np.arange(n_shards, dtype=np.int32),
                     n_chassis // n_shards)


def rho_pool_from_budget(cluster_budget_w, n_servers: int,
                         model: ServerPowerModel | None = None) -> float:
    """Cluster watt budget -> global power-token pool in rho units.

    The cluster-level twin of `serve.admission.rho_cap_from_budget`:
    tokens are the dynamic-power allowance
    ``(budget - S * P_idle(f_max)) / p_dyn_per_core`` — the ceiling on
    fleet-wide ``sum(p95 * cores)``. None/inf disables (+inf pool)."""
    if cluster_budget_w is None or np.isinf(cluster_budget_w):
        return float("inf")
    model = model or ServerPowerModel()
    static = n_servers * float(idle_power(F_MAX))
    return max((float(cluster_budget_w) - static) / model.p_dyn_per_core,
               0.0)


def resource_pool_from_budget(budget: ResourceVector, n_servers: int,
                              model: ServerPowerModel | None = None
                              ) -> np.ndarray:
    """Cluster `ResourceVector` budget -> (R,) global token pool.

    The watts axis converts through the power model exactly like
    `rho_pool_from_budget` (rho units); the cores/GB axes are already
    in pool currency (allocatable virtual cores / GB fleet-wide).
    ``None`` axes disable (+inf) — `ResourceVector(watts=B)` is the
    legacy scalar pool, which the per-axis compares reproduce bit for
    bit."""
    vec = budget.as_array()
    vec[0] = rho_pool_from_budget(
        budget.watts, n_servers, model)
    return vec


def shard_state(state: DeviceClusterState, n_shards: int,
                rho_cap=None, pool_total=None) -> ShardedState:
    """Partition a `DeviceClusterState` into N shard slices.

    Servers are regrouped chassis-major (the order of
    `DeviceClusterState.chassis_servers`, which for the standard
    ``chassis = server // blades`` layout is the server-id order, so
    1-shard tie-breaking matches the unsharded scan exactly).
    `rho_cap`: global per-chassis admission ceiling — (C,) watt-axis
    or (C, R) per-resource, lifted with +inf axes (None = all +inf);
    `pool_total`: global token pool — scalar rho units or (R,) per
    resource (None = +inf), each axis split equally across shards."""
    dtype = state.free_cores.dtype
    n_chassis, k = state.chassis_servers.shape
    n_servers = state.n_servers
    chassis_to_shard(n_chassis, n_shards)       # validates divisibility
    c_loc = n_chassis // n_shards
    s_loc = c_loc * k
    global_chassis = jnp.arange(n_chassis, dtype=jnp.int32) \
        .reshape(n_shards, c_loc)
    global_server = state.chassis_servers.reshape(n_shards, s_loc)
    local_chassis_of = jnp.broadcast_to(
        (jnp.arange(s_loc, dtype=jnp.int32) // k)[None],
        (n_shards, s_loc))
    local_chassis_servers = jnp.broadcast_to(
        jnp.arange(s_loc, dtype=jnp.int32).reshape(c_loc, k)[None],
        (n_shards, c_loc, k))
    shards = DeviceClusterState(
        free_cores=state.free_cores[global_server],
        gamma_uf=state.gamma_uf[global_server],
        gamma_nuf=state.gamma_nuf[global_server],
        res_peak=state.res_peak[global_chassis],
        rho_max=state.rho_max[global_chassis],
        chassis_of=local_chassis_of,
        chassis_servers=local_chassis_servers,
        mem_nuf=state.mem_nuf[global_chassis])
    flat = global_server.reshape(-1)
    shard_of = jnp.zeros(n_servers, jnp.int32).at[flat].set(
        jnp.repeat(jnp.arange(n_shards, dtype=jnp.int32), s_loc))
    local_of = jnp.zeros(n_servers, jnp.int32).at[flat].set(
        jnp.tile(jnp.arange(s_loc, dtype=jnp.int32), n_shards))
    if rho_cap is None:
        cap = jnp.full((n_shards, c_loc, N_RESOURCES), jnp.inf, dtype)
    else:
        cap = lift_caps(jnp.asarray(rho_cap, dtype),
                        xp=jnp)[global_chassis]
    if pool_total is None:
        pool = jnp.full((n_shards, N_RESOURCES), jnp.inf, dtype)
    else:
        total = lift_pool(jnp.asarray(pool_total, dtype), xp=jnp)
        pool = jnp.broadcast_to(div(total[None, :], n_shards, jnp),
                                (n_shards, N_RESOURCES))
    return ShardedState(shards, global_server, global_chassis, shard_of,
                        local_of, cap, pool)


def unshard_state(sharded: ShardedState) -> DeviceClusterState:
    """Reassemble the global `DeviceClusterState` view (diagnostics,
    headroom reporting — the serving path never needs it)."""
    sh = sharded.shards
    dtype = sh.free_cores.dtype
    n, s_loc = sharded.global_server.shape
    c_loc, k = sh.chassis_servers.shape[1:]
    n_servers, n_chassis = n * s_loc, n * c_loc
    srv = sharded.global_server.reshape(-1)
    cha = sharded.global_chassis.reshape(-1)
    chassis_of = jnp.zeros(n_servers, jnp.int32).at[srv].set(
        jnp.take_along_axis(sharded.global_chassis, sh.chassis_of,
                            axis=1).reshape(-1))
    chassis_servers = jnp.zeros((n_chassis, k), jnp.int32).at[cha].set(
        sharded.global_server.reshape(n * c_loc, k))
    return DeviceClusterState(
        free_cores=jnp.zeros(n_servers, dtype).at[srv].set(
            sh.free_cores.reshape(-1)),
        gamma_uf=jnp.zeros(n_servers, dtype).at[srv].set(
            sh.gamma_uf.reshape(-1)),
        gamma_nuf=jnp.zeros(n_servers, dtype).at[srv].set(
            sh.gamma_nuf.reshape(-1)),
        res_peak=jnp.zeros((n_chassis, N_RESOURCES), dtype).at[cha].set(
            sh.res_peak.reshape(-1, N_RESOURCES)),
        rho_max=jnp.zeros(n_chassis, dtype).at[cha].set(
            sh.rho_max.reshape(-1)),
        chassis_of=chassis_of, chassis_servers=chassis_servers,
        mem_nuf=jnp.zeros(n_chassis, dtype).at[cha].set(
            sh.mem_nuf.reshape(-1)))


def shard_mesh(n_shards: int):
    """1-D ``("shard",)`` mesh over the first N devices, or None when
    the runtime has fewer devices than shards (the vmap path then runs
    all shards on one device with identical semantics)."""
    devices = jax.devices()
    if len(devices) < n_shards:
        return None
    return Mesh(np.asarray(devices[:n_shards]), (SHARD_AXIS,))


@lru_cache(maxsize=None)
def _query_fn(meta: ServiceMeta, kernel: str, mesh):
    spec = P(SHARD_AXIS)

    def fn(packed, x):
        return jax.shard_map(
            lambda pk, xs: served_query(pk, meta, xs, kernel=kernel),
            mesh=mesh, in_specs=(P(), spec), out_specs=spec,
            check_vma=False)(packed, x)
    return jax.jit(fn)


def query_sharded(packed: PackedService, meta: ServiceMeta, x,
                  kernel: str, mesh: Mesh) -> dict:
    """`served_query` with the batch split over the mesh's shard axis
    and the model replicated: each device runs the forests on its
    slice of the batch. A Pallas (Mosaic) kernel is never partitioned
    automatically, so on a mesh inference must run inside
    `shard_map`. Rows are independent, so the result equals the
    one-device `served_query` row for row."""
    return _query_fn(meta, kernel, mesh)(packed, x)


def device_put_sharded_state(sharded: ShardedState,
                             mesh: Mesh) -> ShardedState:
    """Pin each shard's slice of the stacked state to its mesh device
    (leading axis over SHARD_AXIS; the inverse-lookup tables are
    replicated), so the per-round jit starts from resident operands
    instead of resharding on entry."""
    row = NamedSharding(mesh, P(SHARD_AXIS))
    rep = NamedSharding(mesh, P())
    stacked = jax.tree.map(lambda x: jax.device_put(x, row),
                           (sharded.shards, sharded.global_server,
                            sharded.global_chassis, sharded.res_cap,
                            sharded.pool))
    inv = jax.tree.map(lambda x: jax.device_put(x, rep),
                       (sharded.shard_of_server,
                        sharded.local_of_server))
    return ShardedState(stacked[0], stacked[1], stacked[2], inv[0],
                        inv[1], stacked[3], stacked[4])


def route_shard(n_arrivals: int, n_shards: int, rnd: int = 0) \
        -> np.ndarray:
    """(B,) target shard of each arrival in spillover round `rnd`.

    Round 0 is the home assignment ``i % n_shards``; later rounds
    rotate (``+ rnd``), a bijection on shards, so every round keeps at
    most ``B / n_shards`` arrivals per shard — shapes never overflow
    the phase-1 slots."""
    return ((np.arange(n_arrivals) + rnd) % n_shards).astype(np.int32)


def _pack_round(pending: np.ndarray, targets: np.ndarray, n_shards: int,
                b_loc: int):
    """Per-shard slot assignment for one protocol round: (N, B/N)
    arrival-index and attempt-mask arrays, arrival order preserved
    within each shard."""
    idx = np.zeros((n_shards, b_loc), np.int32)
    attempt = np.zeros((n_shards, b_loc), bool)
    for s in range(n_shards):
        mine = pending[targets[pending] == s]
        idx[s, :len(mine)] = mine
        attempt[s, :len(mine)] = True
    return idx, attempt


@lru_cache(maxsize=None)
def _round_fn(policy: SchedulerPolicy, cps: float, mesh, ecfg=None):
    """Compiled one-round kernel: gather each shard's routed slice,
    place it on the local state (vmap or shard_map over SHARD_AXIS),
    translate winners to global server ids.

    With `ecfg` (a static `emergency.EmergencyConfig`) the kernel
    additionally takes the per-shard emergency state and queued
    (N, W, C/N) cap-sample windows and steps them *ahead of the
    placement scan in the same dispatch*
    (`placement._apply_cap_windows`) — the fused form the pipeline
    routes the home round through, so an emergency sweep costs zero
    extra vmap/shard_map dispatches. Spillover rounds use the plain
    (``ecfg=None``) kernel: the windows apply exactly once. The fused
    kernel's fifth output is the per-shard
    `placement.SweepCounters` (leading (N,) axis) — the in-scan
    observables of the sweep."""
    place = partial(_place_batch_impl, policy=policy, cps=cps)

    def one_shard(st, pool, cores, is_uf, p95, mem, attempt, cap,
                  *caps):
        if ecfg is None:
            return place(st, pool, cores, is_uf, p95, mem, attempt,
                         cap)
        emer, pw, mask, ts = caps
        emer2, sweep = _apply_cap_windows(ecfg, st, emer, pw, mask, ts)
        st2, srv, pool2 = place(st, pool, cores, is_uf, p95, mem,
                                attempt, cap)
        return st2, srv, pool2, emer2, sweep

    n_in = 8 if ecfg is None else 12
    n_out = 3 if ecfg is None else 5

    def fn(shards, pool, global_server, res_cap, idx, attempt, cores,
           is_uf, p95, mem, *caps):
        c, u, p, m = cores[idx], is_uf[idx], p95[idx], mem[idx]
        operands = (shards, pool, c, u, p, m, attempt, res_cap) + caps
        if mesh is None:
            out = jax.vmap(one_shard)(*operands)
        else:
            def per(*args):
                sq = partial(jax.tree.map, lambda x: x[0])
                res = one_shard(*(sq(a) for a in args))
                return jax.tree.map(lambda x: x[None], res)
            spec = P(SHARD_AXIS)
            out = jax.shard_map(per, mesh=mesh,
                                in_specs=(spec,) * n_in,
                                out_specs=(spec,) * n_out,
                                check_vma=False)(*operands)
        st2, srv, pool2 = out[:3]
        glob = jnp.take_along_axis(global_server, jnp.maximum(srv, 0),
                                   axis=1)
        glob = jnp.where(srv >= 0, glob, srv)
        if ecfg is None:
            return st2, pool2, glob
        return st2, pool2, glob, out[3], out[4]

    return jax.jit(fn)


def place_group_sharded(sharded: ShardedState, cores, is_uf, p95_eff,
                        valid, policy: SchedulerPolicy,
                        cores_per_server: int, *, mem_gb=None,
                        mesh=None, spill_rounds: int | None = None,
                        rebalance: bool = True, emer=None, caps=None,
                        ecfg=None, registry=None):
    """Place one arrival batch through the full sharded protocol.

    cores/is_uf/p95_eff/valid: (B,) host arrays with B divisible by
    the shard count (`valid=False` rows are padding). Runs the home
    round plus up to ``spill_rounds`` (default N-1) spillover rounds —
    an arrival therefore fails only if *every* shard rejected it, so
    sharding never invents capacity failures the single-shard oracle
    would not have (the regret is in objective quality, not
    feasibility; docs/sharding.md). `rebalance` equalizes leftover
    tokens across shards between rounds (conserves the total).

    `emer`/`caps`/`ecfg` fuse the power-emergency sweep into the home
    round's dispatch: `caps` is ``(pw, mask, ts)`` stacked (N, W, C/N)
    sample windows (the `split_caps` layout, one row per queued
    unique-chassis window in merged order) and `emer` the per-shard
    `EmergencyState`. The windows step *before* the placement scan in
    the same compiled call — bit-identical to W standalone
    `apply_caps_sharded` dispatches, because caps touch only the
    emergency state and the criticality aggregates are the pre-batch
    ones either way. Spillover rounds always run the plain kernel.

    Returns ``(sharded_state, servers, info)``: servers is (B,) global
    ids with FAIL_* codes (a still-failed arrival reports the
    most-severe code it saw across rounds), info counts
    ``{"rounds", "spilled", "spill_admitted", "tokens_drawn",
    "tokens_drawn_vec"}`` (tokens_drawn: watt-axis pool draw across
    rounds in rho units, 0.0 with no budget; tokens_drawn_vec: the
    full (R,) per-resource draw — only finite-pool axes report).
    `mem_gb` is the optional (B,) GB demand (None places zero GB —
    the GB ledger axis then never moves). With `emer` it returns ``(sharded_state, servers,
    info, emergency_state, sweep)`` where sweep is a host-side
    `placement.SweepCounters` summed over shards. `registry`, a
    `repro.obs.MetricsRegistry`, counts each compiled round dispatch
    into ``serve_dispatch_total{kind=...}`` at the true call site —
    the first-class replacement for monkeypatch dispatch counting."""
    n = sharded.n_shards
    cores = np.asarray(cores, np.float64)
    is_uf = np.asarray(is_uf, bool)
    p95_eff = np.asarray(p95_eff, np.float64)
    valid = np.asarray(valid, bool)
    b = len(cores)
    if b % n:
        raise ValueError(f"batch size {b} not divisible by {n} shards")
    b_loc = b // n
    if spill_rounds is None:
        spill_rounds = n - 1
    fn = _round_fn(policy, float(cores_per_server), mesh)
    dtype = sharded.shards.free_cores.dtype
    cores_d = jnp.asarray(cores, dtype)
    uf_d = jnp.asarray(is_uf)
    p95_d = jnp.asarray(p95_eff, dtype)
    mem_d = jnp.zeros_like(cores_d) if mem_gb is None \
        else jnp.asarray(np.asarray(mem_gb, np.float64), dtype)
    fused = emer is not None
    if fused:
        fn0 = _round_fn(policy, float(cores_per_server), mesh, ecfg)
        pw, mask, ts = (jnp.asarray(a) for a in caps)
        sweep = None

    result = np.full(b, FAIL_CAPACITY, np.int64)
    pending = np.arange(b)[valid]
    shards, pool = sharded.shards, sharded.pool
    pool_start = np.asarray(pool)
    info = {"rounds": 0, "spilled": 0, "spill_admitted": 0,
            "tokens_drawn": 0.0,
            "tokens_drawn_vec": np.zeros(pool_start.shape[-1])}
    for rnd in range(spill_rounds + 1):
        if not len(pending) and not (rnd == 0 and fused):
            break
        if rnd > 0:
            info["spilled"] += len(pending)
            if rebalance:
                # equalize per axis across shards (conserves each
                # axis total; +inf axes stay +inf)
                pool = jnp.broadcast_to(pool.mean(axis=0)[None, :],
                                        pool.shape)
        targets = route_shard(b, n, rnd)
        idx, attempt = _pack_round(pending, targets, n, b_loc)
        operands = (shards, pool, sharded.global_server,
                    sharded.res_cap, jnp.asarray(idx),
                    jnp.asarray(attempt), cores_d, uf_d, p95_d, mem_d)
        if rnd == 0 and fused:
            shards, pool, glob, emer, sw = fn0(*operands, emer, pw,
                                               mask, ts)
            sweep = SweepCounters(*(np.asarray(x).sum(axis=0)
                                    for x in sw))
            if registry is not None:
                registry.counter("serve_dispatch_total",
                                 kind="sharded_round_caps").inc()
        else:
            shards, pool, glob = fn(*operands)
            if registry is not None:
                registry.counter("serve_dispatch_total",
                                 kind="sharded_round").inc()
        out = np.asarray(glob)[attempt]
        arrivals = idx[attempt]
        admitted = out >= 0
        result[arrivals[admitted]] = out[admitted]
        if rnd > 0:
            info["spill_admitted"] += int(admitted.sum())
        failed = arrivals[~admitted]
        # keep the most severe failure reason seen across rounds
        result[failed] = np.minimum(result[failed], out[~admitted])
        pending = np.sort(failed)
        info["rounds"] = rnd + 1
    pool_end = np.asarray(pool)
    # rebalancing conserves each axis total, so the overall per-axis
    # delta is exactly the admitted draw of every round combined;
    # +inf (unbudgeted) axes report 0
    finite = np.isfinite(pool_start).all(axis=0)
    drawn = np.where(finite, pool_start.sum(axis=0)
                     - np.where(finite, pool_end, 0.0).sum(axis=0), 0.0)
    info["tokens_drawn_vec"] = drawn
    info["tokens_drawn"] = float(drawn[0])
    new = sharded._replace(shards=shards, pool=pool)
    if fused:
        # the home round always runs when fused (it must apply the
        # queued windows even with zero pending arrivals)
        return new, result, info, emer, sweep
    return new, result, info


def split_departures(sharded: ShardedState, servers, cores, p95_eff,
                     is_uf, mem_gb=None):
    """Host-side routing of a global departure batch into per-shard
    local batches — the pre-merge step the ingest subsystem
    (`serve.ingest`, DESIGN.md §11) hands each shard.

    servers: (B,) global ids (negative codes dropped). Returns
    ``(local_srv, cores, p95_eff, is_uf, mem_gb)`` stacked (N, B)
    arrays, padded with ``local_srv = -1`` rows; each shard's rows
    keep the input (merged-stream) order. Shapes stay (N, B) so the
    consuming jit never re-specializes on per-shard counts."""
    servers = np.asarray(servers)
    b = len(servers)
    n = sharded.n_shards
    live = servers >= 0
    safe = np.where(live, servers, 0).astype(np.int64)
    owner = np.where(live, np.asarray(sharded.shard_of_server)[safe], -1)
    local = np.asarray(sharded.local_of_server)[safe]
    srv_out = np.full((n, b), -1, np.int32)
    cores_out = np.zeros((n, b), np.float64)
    p95_out = np.zeros((n, b), np.float64)
    uf_out = np.zeros((n, b), bool)
    mem_out = np.zeros((n, b), np.float64)
    cores = np.asarray(cores, np.float64)
    p95_eff = np.asarray(p95_eff, np.float64)
    is_uf = np.asarray(is_uf, bool)
    mem = np.zeros(b) if mem_gb is None else np.asarray(mem_gb,
                                                        np.float64)
    for s in range(n):
        mine = owner == s
        k = int(mine.sum())
        srv_out[s, :k] = local[mine]
        cores_out[s, :k] = cores[mine]
        p95_out[s, :k] = p95_eff[mine]
        uf_out[s, :k] = is_uf[mine]
        mem_out[s, :k] = mem[mine]
    return srv_out, cores_out, p95_out, uf_out, mem_out


@jax.jit
def _consume_departures(shards, pool, srv, cores, p95_eff, is_uf, mem):
    def per_shard(st, pl, s, c, p, u, m):
        dtype = st.free_cores.dtype
        live = (s >= 0).astype(dtype)
        c_live = c.astype(dtype) * live
        w = p.astype(dtype) * c_live
        credit = jnp.stack([w.sum(), c_live.sum(),
                            (m.astype(dtype) * live).sum()])
        return remove_batch(st, s, c, p, u, m), pl + credit
    return jax.vmap(per_shard)(shards, pool, srv, cores, p95_eff,
                               is_uf, mem)


def consume_departures(sharded: ShardedState, local_srv, cores,
                       p95_eff, is_uf, mem_gb=None) -> ShardedState:
    """Consume per-shard departure batches (the `split_departures` /
    ingest-merge format): one vmapped kernel per shard applies
    `remove_batch` to its own rows and credits the freed (R,) demand
    vector — ``(p95*cores, cores, GB)`` — back to its own pool *in
    the same scan*, one axis at a time, so per-resource token totals
    are conserved. No shard ever sees another shard's departures, and
    no (N, B) broadcast of the full global batch is materialized on
    device."""
    dtype = sharded.shards.free_cores.dtype
    cores_d = jnp.asarray(cores, dtype)
    shards, pool = _consume_departures(
        sharded.shards, sharded.pool, jnp.asarray(local_srv, jnp.int32),
        cores_d, jnp.asarray(p95_eff, dtype),
        jnp.asarray(is_uf),
        jnp.zeros_like(cores_d) if mem_gb is None
        else jnp.asarray(mem_gb, dtype))
    return sharded._replace(shards=shards, pool=pool)


def remove_sharded(sharded: ShardedState, servers, cores, p95_eff,
                   is_uf, mem_gb=None) -> ShardedState:
    """Sharded twin of `serve.placement.remove_batch`: route each
    departure to its owner shard (negative server codes are ignored)
    and credit the freed (R,) demand vector back to that shard's
    pool per axis. Composition of `split_departures` +
    `consume_departures` — the per-shard batches the cross-host
    ingest merge produces directly skip the split."""
    return consume_departures(
        sharded, *split_departures(sharded, servers, cores, p95_eff,
                                   is_uf, mem_gb))


# --- sharded power-emergency plane (DESIGN.md §12) ------------------------

def init_emergency_sharded(n_chassis: int, n_shards: int,
                           dtype=jnp.float32):
    """Emergency state partitioned like the cluster: one
    `serve.emergency.EmergencyState` slice per shard, leading (N,)
    axis over the same contiguous chassis blocks as `shard_state`."""
    chassis_to_shard(n_chassis, n_shards)       # validates divisibility
    return emergency.init_emergency(
        n_chassis // n_shards, batch_shape=(n_shards,), xp=jnp,
        dtype=dtype)


def split_caps(sharded: ShardedState, chassis, power_w, t):
    """Host-side routing of a global power-sample batch into the dense
    per-shard `masked_step` operands: ``(power (N, C/N), mask
    (N, C/N), t (N, C/N))``. Chassis within the batch must be unique
    (the pipeline splits duplicate-bearing windows into sub-windows
    first); ownership is the contiguous-block layout of
    `chassis_to_shard`."""
    n = sharded.n_shards
    c_loc = sharded.global_chassis.shape[1]
    chassis = np.asarray(chassis, np.int64)
    pw = np.zeros((n, c_loc), np.float64)
    mask = np.zeros((n, c_loc), bool)
    ts = np.zeros((n, c_loc), np.float64)
    owner, local = chassis // c_loc, chassis % c_loc
    pw[owner, local] = np.asarray(power_w, np.float64)
    mask[owner, local] = True
    ts[owner, local] = np.asarray(t, np.float64)
    return pw, mask, ts


@lru_cache(maxsize=None)
def _caps_fn(cfg: emergency.EmergencyConfig, mesh):
    """Compiled sharded emergency scan: derive each shard's per-chassis
    per-criticality commitments from its own aggregates and run the
    masked emergency step — vmap on one device (the semantics oracle),
    shard_map over the mesh (identical per-shard arithmetic)."""

    def one_shard(st, emer, pw, mask, ts):
        rho_lv = emergency.chassis_rho_levels(
            st.gamma_nuf, st.gamma_uf, st.chassis_servers, jnp)
        return emergency.masked_step(cfg, emer, rho_lv, pw, mask, ts,
                                     jnp)

    def fn(shards, emer, pw, mask, ts):
        if mesh is None:
            return jax.vmap(one_shard)(shards, emer, pw, mask, ts)

        def per(st, em, p1, m1, t1):
            sq = partial(jax.tree.map, lambda x: x[0])
            e2, o2 = one_shard(sq(st), sq(em), p1[0], m1[0], t1[0])
            return jax.tree.map(lambda x: x[None], (e2, o2))
        spec = P(SHARD_AXIS)
        return jax.shard_map(per, mesh=mesh, in_specs=(spec,) * 5,
                             out_specs=(spec, spec),
                             check_vma=False)(shards, emer, pw, mask, ts)

    return jax.jit(fn)


def init_adaptive_sharded(cfg, n_chassis: int, n_shards: int,
                          dtype=jnp.float32):
    """Adaptive-controller state partitioned like the cluster: one
    `serve.adaptive.AdaptiveState` slice per shard, leading (N,) axis
    over the same contiguous chassis blocks as `shard_state` — each
    shard carries its *own* ratio over the budget slice it owns."""
    from repro.serve import adaptive
    chassis_to_shard(n_chassis, n_shards)       # validates divisibility
    return adaptive.init_adaptive(
        cfg, n_chassis // n_shards, batch_shape=(n_shards,), xp=jnp,
        dtype=dtype)


@lru_cache(maxsize=None)
def _adaptive_fn(cfg, mesh):
    """Compiled sharded adaptive-controller scan
    (`serve.adaptive.adaptive_step` per shard): each shard scores its
    own chassis windows and steps its own ratio — vmap on one device,
    shard_map over the mesh, identical per-shard arithmetic (the
    `_caps_fn` pattern)."""
    from repro.serve import adaptive

    def one_shard(st, ast, pw, mask):
        rho_lv = emergency.chassis_rho_levels(
            st.gamma_nuf, st.gamma_uf, st.chassis_servers, jnp)
        return adaptive.adaptive_step(cfg, ast, rho_lv, pw, mask, jnp)

    def fn(shards, ast, pw, mask):
        if mesh is None:
            return jax.vmap(one_shard)(shards, ast, pw, mask)

        def per(st, a1, p1, m1):
            sq = partial(jax.tree.map, lambda x: x[0])
            a2, o2 = one_shard(sq(st), sq(a1), p1[0], m1[0])
            return jax.tree.map(lambda x: x[None], (a2, o2))
        spec = P(SHARD_AXIS)
        return jax.shard_map(per, mesh=mesh, in_specs=(spec,) * 4,
                             out_specs=(spec, spec),
                             check_vma=False)(shards, ast, pw, mask)

    return jax.jit(fn)


def apply_adaptive_sharded(cfg, sharded: ShardedState, ast, chassis,
                           power_w, *, mesh=None):
    """Apply one unique-chassis power-sample window to the sharded
    adaptive-controller state (`serve.adaptive`, DESIGN.md §15): route
    samples to their owner shards (`split_caps`) and run every shard's
    stability-scoring + ratio step concurrently — no cross-shard
    communication; each shard adapts the slice of the watt budget it
    owns. Returns ``(new_adaptive_state, AdaptiveOutputs)`` with the
    per-shard leading axis."""
    dtype = sharded.shards.free_cores.dtype
    pw, mask, _ = split_caps(sharded, chassis, power_w,
                             np.zeros(len(np.asarray(chassis))))
    fn = _adaptive_fn(cfg, mesh)
    return fn(sharded.shards, ast, jnp.asarray(pw, dtype),
              jnp.asarray(mask))


def apply_caps_sharded(cfg: emergency.EmergencyConfig,
                       sharded: ShardedState, emer, chassis, power_w,
                       t, *, mesh=None):
    """Apply one unique-chassis power-sample window to the sharded
    emergency state: route samples to their owner shards
    (`split_caps`) and run every shard's alarm + apportionment kernel
    concurrently — no cross-shard communication, because chassis
    ownership is exclusive and each shard's criticality aggregates are
    local. Returns ``(new_emergency_state, EmergencyOutputs)`` with
    the per-shard leading axis."""
    dtype = sharded.shards.free_cores.dtype
    pw, mask, ts = split_caps(sharded, chassis, power_w, t)
    fn = _caps_fn(cfg, mesh)
    return fn(sharded.shards, emer, jnp.asarray(pw, dtype),
              jnp.asarray(mask), jnp.asarray(ts, dtype))


def init_ballooning_sharded(n_chassis: int, n_shards: int,
                            dtype=jnp.float32):
    """Ballooning state partitioned like the cluster (leading (N,)
    axis over the same contiguous chassis blocks as `shard_state` —
    the `init_emergency_sharded` layout)."""
    chassis_to_shard(n_chassis, n_shards)       # validates divisibility
    return ballooning.init_ballooning(
        n_chassis // n_shards, batch_shape=(n_shards,), xp=jnp,
        dtype=dtype)


@lru_cache(maxsize=None)
def _caps_balloon_fn(ecfg: emergency.EmergencyConfig,
                     bcfg: ballooning.BallooningConfig, mesh):
    """Compiled sharded balloon-then-cap scan: each shard balloons its
    alarmed chassis against its own NUF-memory ledger
    (`serve.ballooning.balloon_step` over ``shards.mem_nuf``), then
    runs the masked emergency step on the DRAM-adjusted draws — vmap
    on one device, shard_map over the mesh (the `_caps_fn` pattern)."""

    def one_shard(st, emer, bst, pw, mask, ts):
        rho_lv = emergency.chassis_rho_levels(
            st.gamma_nuf, st.gamma_uf, st.chassis_servers, jnp)
        bst2, bout = ballooning.balloon_step(
            bcfg, ecfg, bst, rho_lv, pw, st.mem_nuf, mask, jnp)
        emer2, eout = emergency.masked_step(
            ecfg, emer, rho_lv, bout.power_adj_w, mask, ts, jnp)
        return emer2, bst2, eout, bout

    def fn(shards, emer, bst, pw, mask, ts):
        if mesh is None:
            return jax.vmap(one_shard)(shards, emer, bst, pw, mask, ts)

        def per(st, em, b1, p1, m1, t1):
            sq = partial(jax.tree.map, lambda x: x[0])
            out = one_shard(sq(st), sq(em), sq(b1), p1[0], m1[0], t1[0])
            return jax.tree.map(lambda x: x[None], out)
        spec = P(SHARD_AXIS)
        return jax.shard_map(per, mesh=mesh, in_specs=(spec,) * 6,
                             out_specs=(spec,) * 4,
                             check_vma=False)(shards, emer, bst, pw,
                                              mask, ts)

    return jax.jit(fn)


def apply_caps_ballooned_sharded(ecfg: emergency.EmergencyConfig,
                                 bcfg: ballooning.BallooningConfig,
                                 sharded: ShardedState, emer, bst,
                                 chassis, power_w, t, *, mesh=None):
    """`apply_caps_sharded` with the ballooning rung in front
    (DESIGN.md §16): the window's samples are first offered to
    `serve.ballooning.balloon_step` — alarmed chassis reclaim NUF
    memory to absorb the cut the NUF frequency floor cannot — and the
    masked emergency step consumes the DRAM-adjusted draws. Returns
    ``(emergency_state, balloon_state, EmergencyOutputs,
    BalloonOutputs)``, all with the per-shard leading axis."""
    dtype = sharded.shards.free_cores.dtype
    pw, mask, ts = split_caps(sharded, chassis, power_w, t)
    fn = _caps_balloon_fn(ecfg, bcfg, mesh)
    return fn(sharded.shards, emer, bst, jnp.asarray(pw, dtype),
              jnp.asarray(mask), jnp.asarray(ts, dtype))
