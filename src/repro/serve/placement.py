"""Vectorized Algorithm-1 placement (serve-pipeline stage 3).

`place_batch` is the jnp twin of `SchedulerPolicy.choose` +
`ClusterState.place`: one jitted `lax.scan` walks an arrival
micro-batch in order (placements must see earlier placements — the
same sequential semantics as the event-driven scheduler), and each
step scores *all* servers at once.

The rank-weight aggregation is reformulated sort-free AND
scatter-free, because a per-step argsort is the one operation XLA
cannot make fast inside a scan (~150 us per 720-element sort on CPU —
25x the whole step budget) and a per-step `.at[].set` scatter is the
next worst (~45 us each on the XLA CPU backend, vs ~1 us for the
gathers / cumsums / fused compares everything below is built from):

  * a placement only changes the scores of the placed chassis'
    K = S/C servers (its kappa, plus the chosen server's packing/eta
    term), so the order structures are *maintained incrementally* — no
    sort after the one batched argsort that seeds the scan. The
    packing rank row recounts the one moved key exactly; the two power
    orders are carried as *inverse* permutations (rank position ->
    server) plus the score-by-server table: the K moved servers'
    landing and vacated positions come from a fused O(K log S) binary
    search over the carried order (`_delta_positions`), and every
    surviving server keeps its relative order, so the recomposition is
    closed-form complement indexing via a histogram + shared prefix
    sum (`_compose_inverse`) — no S-sized scatter, no O(S*K) pass, no
    window search;
  * per-arrival feasibility and the objective are evaluated in power
    *rank-position* space, so forward power ranks never need to
    exist: gathering the feasibility mask through the inverse
    permutation and prefix-counting it yields the power subset rank
    at every position (gather + cumsum — branchless, no lax.cond,
    identical integers on every path), and the packing subset rank is
    exactly `full_rank - n_infeasible` because infeasible servers are
    strictly fuller and hold a contiguous prefix of the packing
    order;
  * the objective then mirrors `SchedulerPolicy.choose` operation for
    operation — `sum_r w_r * (1 - subset_rank_r/(n_feas-1))`, first
    argmax by server index (= min server id over float-maximal
    positions) — because even exactly-tied integer rank sums can
    resolve differently once divided and weighted in floats.

Rank rows are (packing, power-for-UF, power-for-NUF) — the power score
depends on the arriving VM's type, so both orders are maintained.
Single-rule policies (packing_weight or power_weight zero, or the
power rule off) skip the rank machinery entirely: one rule's rank
weight is a monotone transform of its raw score, so a stable score
argmax decides (`_place_batch_single_rule`).

Decision equivalence with the numpy path holds because subset ranks
are exact integers and the float aggregation replicates the host
arithmetic; the scheduler simulation's serve backend runs this same
scan in x64, where it is bit-equivalent to the f64 host rule
(DESIGN.md §9 bounds the residual f32-vs-f64 divergence of the score
inputs on the serving path).

The power-headroom admission check (serve-pipeline stage 4, see
`serve/admission.py`) is fused into the scan: a placement that would
push its chassis' projected peak draw over budget is rejected before
it mutates the state.
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.placement import ClusterState, SchedulerPolicy
from repro.core.resources import N_RESOURCES, lift_caps, lift_pool
from repro.core.rounding import div
from repro.serve import emergency

#: `place_batch` outcome codes (in the returned server array).
FAIL_CAPACITY = -1      # no feasible server (deployment failure)
FAIL_POWER = -2         # a chassis resource ceiling rejected (any axis)
FAIL_TOKENS = -3        # shard's token pool exhausted (any axis)


class DeviceClusterState(NamedTuple):
    """Device mirror of `core.placement.ClusterState`'s aggregates,
    generalized to the (R,)-axis resource ledger (DESIGN.md §16):
    `res_peak` tracks committed (rho, cores, GB) per chassis — axis 0
    is the legacy ``rho_peak`` (exposed as a property so the scoring
    rules and diagnostics read it unchanged), and `mem_nuf` carries
    the NUF slice of the GB axis, the balloonable headroom the
    emergency ladder's middle rung reclaims (`serve.ballooning`)."""
    free_cores: jnp.ndarray      # (S,) f32
    gamma_uf: jnp.ndarray        # (S,) f32
    gamma_nuf: jnp.ndarray       # (S,) f32
    res_peak: jnp.ndarray        # (C, R) f32 — committed (rho, cores, GB)
    rho_max: jnp.ndarray         # (C,) f32
    chassis_of: jnp.ndarray      # (S,) i32
    chassis_servers: jnp.ndarray  # (C, S//C) i32 — servers per chassis
    mem_nuf: jnp.ndarray         # (C,) f32 — committed NUF GB

    @property
    def rho_peak(self) -> jnp.ndarray:
        """(C,) committed sum(p95*cores) — the watts axis of the
        ledger, the exact quantity the pre-vector state carried."""
        return self.res_peak[..., 0]

    @property
    def n_servers(self) -> int:
        return self.free_cores.shape[0]


def _chassis_servers(chassis_of: np.ndarray) -> np.ndarray:
    """(C, K) server-index table (rank maintenance gathers the placed
    chassis' servers through it). Chassis must be equal-sized."""
    chassis_of = np.asarray(chassis_of)
    n_chassis = int(chassis_of.max()) + 1
    sizes = np.bincount(chassis_of, minlength=n_chassis)
    assert (sizes == len(chassis_of) // n_chassis).all(), \
        "chassis must be equal-sized"
    order = np.argsort(chassis_of, kind="stable")
    return order.reshape(n_chassis, -1).astype(np.int32)


def device_state(state: ClusterState, dtype=jnp.float32,
                 mem_gb=None, mem_nuf=None) -> DeviceClusterState:
    """Mirror a host `ClusterState`'s aggregates onto the device.
    `dtype` selects the serving (f32) or equivalence-testing (f64,
    under `jax.enable_x64(True)`) arithmetic.

    The host state is the watts/cores oracle; the cores axis of
    `res_peak` is derived from its per-server free cores, and the GB
    axis comes from `mem_gb`/`mem_nuf` ((C,) committed GB — total and
    NUF slice), zeros when the caller tracks no memory."""
    chassis_servers = _chassis_servers(state.chassis_of_server)
    free = np.asarray(state.free_cores, np.float64)
    cores_comm = (float(state.cores_per_server)
                  - free)[chassis_servers].sum(-1)
    n_chassis = chassis_servers.shape[0]
    mem = np.zeros(n_chassis) if mem_gb is None \
        else np.asarray(mem_gb, np.float64)
    res_peak = np.stack([np.asarray(state.rho_peak, np.float64),
                         cores_comm, mem], axis=-1)
    return DeviceClusterState(
        jnp.asarray(state.free_cores, dtype),
        jnp.asarray(state.gamma_uf, dtype),
        jnp.asarray(state.gamma_nuf, dtype),
        jnp.asarray(res_peak, dtype),
        jnp.asarray(state.rho_max, dtype),
        jnp.asarray(state.chassis_of_server, jnp.int32),
        jnp.asarray(chassis_servers),
        jnp.zeros(n_chassis, dtype) if mem_nuf is None
        else jnp.asarray(mem_nuf, dtype))


def fresh_state(n_servers: int, cores_per_server: int,
                chassis_of: np.ndarray) -> DeviceClusterState:
    """Device state of an empty cluster (every core free, nothing
    committed) with the given server→chassis layout."""
    return device_state(ClusterState(
        n_servers=n_servers, cores_per_server=cores_per_server,
        chassis_of_server=np.asarray(chassis_of),
        n_chassis=int(np.asarray(chassis_of).max()) + 1))


def score_chassis_batch(state: DeviceClusterState) -> jnp.ndarray:
    """jnp twin of `ClusterState.score_chassis` — (C,)."""
    return 1.0 - div(state.rho_peak, jnp.maximum(state.rho_max, 1e-9), jnp)


def score_server_batch(state: DeviceClusterState, vm_is_uf,
                       cores_per_server: int) -> jnp.ndarray:
    """jnp twin of `ClusterState.score_server`. `vm_is_uf` may be a
    scalar bool or a (B,) array (then the result is (B, S))."""
    uf = jnp.asarray(vm_is_uf, bool)
    diff = jnp.where(uf[..., None] if uf.ndim else uf,
                     state.gamma_nuf - state.gamma_uf,
                     state.gamma_uf - state.gamma_nuf)
    return 0.5 * (1.0 + div(diff, float(cores_per_server), jnp))


def _rule_scores(state: DeviceClusterState, policy: SchedulerPolicy,
                 cps: float) -> jnp.ndarray:
    """(R, S) score rows the preference rules order. Row 0: packing
    (`core.placement.packing_score`). Rows 1-2 (when the power rule is
    on): Algorithm-1 score for a UF / NUF arrival — both are kept
    because the arriving VM's type flips the eta term."""
    pack = 1.0 - div(state.free_cores, cps, jnp)
    if not policy.use_power_rule:
        return pack[None]
    kappa = score_chassis_batch(state)[state.chassis_of]
    a = policy.alpha
    return jnp.stack(
        [pack] + [a * kappa + (1.0 - a)
                  * score_server_batch(state, uf, cps)
                  for uf in (True, False)])


def _before(s_j, j, s_i, i):
    """Stable descending order: does key (s_j, j) sort before key
    (s_i, i)? Ties break toward the smaller server index — the same
    order `np.argsort(kind='stable')` of negated scores produces."""
    return (s_j > s_i) | ((s_j == s_i) & (j < i))


def _init_ranks(scores: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stable descending ranks (R, S) plus the inverse permutations
    (R, S) (rank position -> server) the scan maintains — one batched
    argsort + scatter, once per micro-batch, outside the scan."""
    r, s = scores.shape
    perm = jnp.argsort(-scores, axis=-1, stable=True)
    rows = jnp.arange(r)[:, None]
    ranks = jnp.zeros((r, s), jnp.int32).at[rows, perm].set(
        jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (r, s)))
    return ranks, perm.astype(jnp.int32)


def _delta_positions(perm: jnp.ndarray, q_prev: jnp.ndarray,
                     new_d: jnp.ndarray, old_d: jnp.ndarray,
                     delta: jnp.ndarray
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Landing + vacated positions of the K moved servers, by fused
    binary search over the carried power orders.

    `perm` (R', S) is rank position -> server, `q_prev` (R', S) the
    score-by-server table consistent with it; the search comparator is
    the stable-descending key ``(score, server-id)`` (`_before`), which
    is a strict total order, so (a) the lower bound of a *new* key
    `new_d` is exactly where it will land once every old delta key is
    deleted and the new ones inserted — after removing the old-key /
    intra-new corrections applied by the caller — and (b) the lower
    bound of an *old* key `old_d` is its exact current position. Both
    searches run fused (one (R', 2K) bracket, ceil(log2(S+1)) rounds
    of two tiny flat gathers) — O(K log S) work, no O(S) pass."""
    rp, s = perm.shape
    k = delta.shape[0]
    nb = max(int(np.ceil(np.log2(s + 1))), 1)
    roff = jnp.arange(rp, dtype=jnp.int32)[:, None] * s
    pflat = perm.reshape(-1)
    qflat = q_prev.reshape(-1)
    keys = jnp.concatenate([new_d, old_d], axis=1)          # (R', 2K)
    ids = jnp.concatenate([delta, delta])[None, :]
    lo = jnp.zeros((rp, 2 * k), jnp.int32)
    hi = jnp.full((rp, 2 * k), s, jnp.int32)
    for _ in range(nb):
        # mid == s only in the degenerate lo == hi == s bracket, where
        # both updates keep lo == hi; clamp so the gather stays legal
        mid = jnp.minimum((lo + hi) >> 1, s - 1)
        sm = pflat[(mid + roff).reshape(-1)].reshape(rp, 2 * k)
        km = qflat[(sm + roff).reshape(-1)].reshape(rp, 2 * k)
        b = _before(km, sm, keys, ids)
        lo = jnp.where(b, mid + 1, lo)
        hi = jnp.where(b, hi, mid)
    return lo[:, :k], lo[:, k:]


def _compose_inverse(perm: jnp.ndarray, fresh: jnp.ndarray,
                     d_old: jnp.ndarray,
                     delta: jnp.ndarray) -> jnp.ndarray:
    """Scatter-light inverse-permutation maintenance.

    `perm` (R', S) holds rank position -> server for R' rank rows;
    after one placement only the K servers of the placed chassis
    (`delta`, vacating old positions `d_old` and landing at new
    positions `fresh`, both (R', K)) move — every other server keeps
    its *relative* order (its pairwise keys are untouched). So the new
    order is: the surviving servers in old order, merged around the K
    landing positions. Position q that is not a landing spot holds the
    j-th survivor (``j = q - #landings <= q``), which sat at the j-th
    old position not vacated — the j-th element of the complement of
    the sorted vacated positions `sd`, in closed form
    ``g = j + #{k: sd[k] - k <= j}``.

    The count term is a table lookup: ``v = sd - arange(K)`` is
    nondecreasing, so a K-element histogram of v plus one prefix sum
    tabulates ``m(j) = #{v <= j}`` for every j at once. Everything is
    flat 1-D (rows concatenated, per-row corrections are the constant
    K each row contributes), so the whole compose is two K-sized
    scatters (XLA CPU folds these; only S-sized scatters hit the ~45us
    cliff), one fused cumsum, and two flat gathers — no sort (K
    elements order via pairwise counts), no O(S*K) pass."""
    rp, s = perm.shape
    k = delta.shape[0]
    qpos = jnp.arange(s, dtype=jnp.int32)
    kpos = jnp.arange(k, dtype=jnp.int32)
    roff = jnp.arange(rp, dtype=jnp.int32)[:, None] * s
    roffh = jnp.arange(rp, dtype=jnp.int32)[:, None] * (s + 1)
    rk_corr = jnp.arange(rp, dtype=jnp.int32)[:, None] * k
    # ascending vacated positions via pairwise-compare counting
    # (positions are distinct: counts are a permutation of 0..K-1)
    rkk = (d_old[:, None, :] < d_old[:, :, None]) \
        .sum(-1, dtype=jnp.int32)                           # (R', K)
    sd = ((rkk[:, None, :] == kpos[None, :, None])
          * d_old[:, None, :]).sum(-1).astype(jnp.int32)
    v = sd - kpos[None, :]                       # nondecreasing, >= 0
    # landing positions: K-sized scatter of server-id + 1 (0 == none)
    mark = jnp.zeros(rp * s, jnp.int32) \
        .at[(fresh + roff).reshape(-1)].set(
            jnp.broadcast_to(delta[None, :] + 1, (rp, k)).reshape(-1)) \
        .reshape(rp, s)
    is_new = mark > 0
    inew = is_new.astype(jnp.int32)
    hist = jnp.zeros(rp * (s + 1), jnp.int32) \
        .at[(v + roffh).reshape(-1)].add(1)
    # one fused prefix sum tabulates both the landing counts and m(j);
    # each row of each segment sums to exactly K, so the cross-row /
    # cross-segment carry is the deterministic correction below
    both = jnp.cumsum(jnp.concatenate([inew.reshape(-1), hist]))
    land_inc = both[:rp * s].reshape(rp, s) - rk_corr       # inclusive
    m_flat = both[rp * s:]
    j_q = qpos[None] - (land_inc - inew)
    m_at = m_flat[(j_q + roffh).reshape(-1)].reshape(rp, s) \
        - rp * k - rk_corr
    g = j_q + m_at
    moved = perm.reshape(-1)[
        (jnp.minimum(g, s - 1) + roff).reshape(-1)].reshape(rp, s)
    return jnp.where(is_new, mark - 1, moved)


def _commit(st: DeviceClusterState, pool, srv, found, cores_i, uf_i,
            p95_i, mem_i, valid_i, res_cap):
    """Admission check + masked state update + outcome code — the
    shared tail of both scan bodies. `srv` is the winning server with
    `found` indicating a feasible candidate existed.

    The admission draw is the (R,) demand vector ``(p95*cores, cores,
    GB)`` (`core.resources.demand_vector`): the chassis ledger check
    and the token-pool reserve both run per axis and every axis must
    clear (`res_cap` is (C, R), `pool` is the shard's (R,) balance —
    +inf axes are vacuous, so a power-only config reproduces the
    scalar watt protocol bit for bit). A reject on *any* axis maps to
    FAIL_POWER (ceiling) / FAIL_TOKENS (pool) before the state
    mutates."""
    dtype = st.free_cores.dtype
    srv = jnp.where(found, srv, 0).astype(jnp.int32)
    ch = st.chassis_of[srv]
    w = p95_i * cores_i
    d = jnp.stack([w, cores_i, mem_i])                         # (R,)
    admit_ch = jnp.all(st.res_peak[ch] + d <= res_cap[ch])
    admit_pool = jnp.all(d <= pool)
    scale = (found & admit_ch & admit_pool & valid_i).astype(dtype)
    uf_f = uf_i.astype(dtype)
    st2 = st._replace(
        free_cores=st.free_cores.at[srv].add(-cores_i * scale),
        gamma_uf=st.gamma_uf.at[srv].add(w * scale * uf_f),
        gamma_nuf=st.gamma_nuf.at[srv].add(w * scale * (1.0 - uf_f)),
        res_peak=st.res_peak.at[ch].add(d * scale),
        mem_nuf=st.mem_nuf.at[ch].add(mem_i * scale * (1.0 - uf_f)))
    pool2 = pool - d * scale
    out = jnp.where(~found, FAIL_CAPACITY,
                    jnp.where(~admit_ch, FAIL_POWER,
                              jnp.where(admit_pool, srv, FAIL_TOKENS)))
    return st2, pool2, out, srv


def _place_batch_single_rule(state, pool, cores, is_uf, p95_eff, mem,
                             valid, res_cap, policy: SchedulerPolicy,
                             cps):
    """Rank-free scan for single-rule policies: the winner is the
    stable argmax of the active rule's raw score over feasible servers
    (exactly `SchedulerPolicy.choose` with the other rule's weight 0,
    e.g. `packing_weight=0` == the paper's literal Algorithm-1 /
    §IV-E preference order)."""
    dtype = state.free_cores.dtype
    pack_only = (not policy.use_power_rule) or policy.power_weight == 0.0
    # no positive rule weight at all: the host objective is identically
    # zero and `choose` returns the first feasible server
    no_rule = pack_only and policy.packing_weight == 0.0
    neg_inf = jnp.asarray(-jnp.inf, dtype)

    def body(carry, inp):
        st, pl = carry
        cores_i, uf_i, p95_i, mem_i, valid_i = inp
        feasible = (st.free_cores >= cores_i) & valid_i
        n_feas = feasible.sum()
        if no_rule:
            score = jnp.zeros_like(st.free_cores)
        elif pack_only:
            score = 1.0 - div(st.free_cores, cps, jnp)
        else:
            kappa = score_chassis_batch(st)[st.chassis_of]
            eta = score_server_batch(st, uf_i, cps)
            score = policy.alpha * kappa + (1.0 - policy.alpha) * eta
        srv = jnp.argmax(jnp.where(feasible, score, neg_inf))
        st2, pl2, out, _ = _commit(st, pl, srv, n_feas > 0, cores_i,
                                   uf_i, p95_i, mem_i, valid_i, res_cap)
        return (st2, pl2), out

    inputs = (jnp.asarray(cores, dtype), jnp.asarray(is_uf, bool),
              jnp.asarray(p95_eff, dtype), jnp.asarray(mem, dtype),
              jnp.asarray(valid, bool))
    (state, pool), servers = jax.lax.scan(body, (state, pool), inputs)
    return state, servers, pool


def _place_batch_impl(state: DeviceClusterState, pool, cores, is_uf,
                      p95_eff, mem, valid, rho_cap,
                      policy: SchedulerPolicy, cps: float):
    """Shared scan implementation behind `place_batch` (pool forced to
    +inf) and `place_batch_pooled`. Pure and transformation-friendly:
    the sharded serve protocol vmaps/shard_maps it across per-shard
    states (`serve.sharding`). `mem` is the (B,) GB demand; `rho_cap`
    may be the legacy (C,) watt-axis ceiling or a full (C, R) resource
    ceiling, and `pool` a scalar rho balance or an (R,) vector — both
    are lifted with vacuous +inf axes (`core.resources`). Returns
    (state, servers, pool_left) with pool_left (R,)."""
    dtype = state.free_cores.dtype
    pool = lift_pool(jnp.asarray(pool, dtype), xp=jnp)
    res_cap = lift_caps(jnp.asarray(rho_cap, dtype), xp=jnp)
    n_servers = state.n_servers
    idx = jnp.arange(n_servers, dtype=jnp.int32)
    use_power = policy.use_power_rule
    pw, qw = policy.packing_weight, policy.power_weight
    # With a single active rule, argmax of its rank weight IS argmax of
    # its raw score (rank is a monotone transform; stable argsort and
    # argmax both break ties toward the smaller server index), so the
    # whole rank machinery compiles away (~10x fewer step ops).
    single_rule = (not use_power) or pw == 0.0 or qw == 0.0
    if single_rule:
        return _place_batch_single_rule(
            state, pool, cores, is_uf, p95_eff, mem, valid, res_cap,
            policy, cps)

    # both rules active implies use_power: the carry holds the packing
    # rank row, the power score-by-server table, and the inverse
    # permutations (rank position -> server) of the two power rows; the
    # objective is evaluated in *position* space, so the forward power
    # ranks never need to exist
    assert n_servers < (1 << 15), \
        "rank/feasibility bit-packing assumes n_servers < 2**15"
    roff2 = jnp.arange(2, dtype=jnp.int32)[:, None] * n_servers
    a = policy.alpha

    def body(carry, inp):
        st, pl, q_prev, pranks, perm = carry
        cores_i, uf_i, p95_i, mem_i, valid_i = inp
        feasible = (st.free_cores >= cores_i) & valid_i
        n_feas = feasible.sum()
        n_out = n_servers - n_feas
        perm_pow = jnp.where(uf_i, perm[0], perm[1])

        # Everything is indexed by power-rank position p (server
        # perm_pow[p]). Subset rank of the power rule is the prefix
        # count of feasibility in rank order; subset rank of the
        # packing rule is exactly rank - n_out, because infeasible
        # servers are strictly *fuller* and hold a contiguous prefix
        # of the packing order. Branchless and exact on every path
        # (all-feasible reduces to prefix[p] counting every p' < p).
        # Packing rank and feasibility ride one gather (bit 15).
        comb = pranks | (feasible.astype(jnp.int32) << 15)
        cg = comb[perm_pow]
        by_rank = cg >= (1 << 15)
        br = by_rank.astype(jnp.int32)
        sr_pow = jnp.cumsum(br) - br
        sr_pack = (cg & 0x7FFF) - n_out.astype(jnp.int32)

        # numpy-bitwise objective: exact integer rank ties can still
        # resolve differently once divided by (n-1) and weighted (the
        # float sums round per operand set), so mirror
        # `core.placement._rank_weight` + `choose` operation for
        # operation. `choose` takes the first argmax by *server*
        # index; in position space that is the smallest server id
        # among the float-maximal feasible positions.
        denom = jnp.maximum(n_feas - 1, 1).astype(dtype)
        one = jnp.asarray(1.0, dtype)
        rw_guard = n_feas == 1

        def rw(sr):
            return jnp.where(rw_guard, one,
                             one - div(sr.astype(dtype), denom, jnp))

        obj = pw * rw(sr_pack) + qw * rw(sr_pow)
        masked = jnp.where(by_rank, obj, jnp.asarray(-jnp.inf, dtype))
        srv = jnp.min(jnp.where(masked == jnp.max(masked), perm_pow,
                                n_servers))
        st2, pl2, out, srv = _commit(st, pl, srv, n_feas > 0, cores_i,
                                     uf_i, p95_i, mem_i, valid_i,
                                     res_cap)
        ch = st.chassis_of[srv]
        # Incremental maintenance. Packing ranks: only the placed
        # server's score moved — subtract its old key's wins over each
        # server, add the new ones, recount the placed row exactly.
        # Power orders: the placed chassis' K servers moved (kappa,
        # plus the placed server's eta) — their new keys are recomputed
        # on the K-subset with the exact `_rule_scores` float ops, the
        # landing/vacated positions come from `_delta_positions`, and
        # the inverse permutations recompose in closed form. A
        # rejected/failed arrival leaves scores unchanged, so every
        # correction cancels to zero.
        p_old = 1.0 - div(st.free_cores, cps, jnp)
        p_new_s = 1.0 - div(st2.free_cores[srv], cps, jnp)
        dcnt0 = _before(p_new_s, srv, p_old, idx).astype(jnp.int32) \
            - _before(p_old[srv], srv, p_old, idx).astype(jnp.int32)
        fresh0 = _before(p_old, idx, p_new_s, srv) \
            .sum(dtype=jnp.int32) \
            - _before(p_old[srv], srv, p_new_s, srv).astype(jnp.int32)
        pranks2 = jnp.where(idx == srv, fresh0, pranks + dcnt0)
        delta = st.chassis_servers[ch]                   # (K,)
        # K-subset twin of `_rule_scores` rows 1-2 (same float ops on
        # the same operands, so the carried table stays bit-identical
        # to a full recompute)
        kappa2 = 1.0 - div(st2.rho_peak[ch],
                           jnp.maximum(st2.rho_max[ch], 1e-9), jnp)
        diff = st2.gamma_nuf[delta] - st2.gamma_uf[delta]
        eta2 = 0.5 * (1.0 + div(jnp.stack([diff, -diff]), cps, jnp))
        new_d = a * kappa2 + (1.0 - a) * eta2            # (2, K)
        old_d = q_prev[:, delta]
        q_prev2 = q_prev.reshape(-1) \
            .at[(delta[None, :] + roff2).reshape(-1)] \
            .set(new_d.reshape(-1)).reshape(2, n_servers)
        lb_new, d_old = _delta_positions(perm, q_prev, new_d, old_d,
                                         delta)
        # lower bound of a new key counts old delta keys and the other
        # new keys that sort before it; remove the former (they leave
        # the order), add this key's rank among the new keys
        before_old = _before(old_d[:, None, :], delta[None, None, :],
                             new_d[:, :, None], delta[None, :, None]) \
            .sum(-1, dtype=jnp.int32)
        intra_new = _before(new_d[:, None, :], delta[None, None, :],
                            new_d[:, :, None], delta[None, :, None]) \
            .sum(-1, dtype=jnp.int32)
        fresh = lb_new - before_old + intra_new
        perm2 = _compose_inverse(perm, fresh, d_old, delta)
        return (st2, pl2, q_prev2, pranks2, perm2), out

    inputs = (jnp.asarray(cores, dtype), jnp.asarray(is_uf, bool),
              jnp.asarray(p95_eff, dtype), jnp.asarray(mem, dtype),
              jnp.asarray(valid, bool))
    scores0 = _rule_scores(state, policy, cps)
    ranks0, perm0 = _init_ranks(scores0)
    (state, pool, _, _, _), servers = jax.lax.scan(
        body, (state, pool, scores0[1:], ranks0[0], perm0[1:]), inputs)
    return state, servers, pool


def _mem_or_zeros(mem_gb, cores):
    """(B,) GB demand; ``None`` (a memory-blind caller) places zero GB
    — every GB compare is then vacuous, preserving legacy decisions."""
    return jnp.zeros(jnp.shape(cores)) if mem_gb is None \
        else jnp.asarray(mem_gb)


@partial(jax.jit, static_argnames=("policy", "cores_per_server"))
def place_batch(state: DeviceClusterState, cores: jnp.ndarray,
                is_uf: jnp.ndarray, p95_eff: jnp.ndarray,
                valid: jnp.ndarray, rho_cap: jnp.ndarray,
                policy: SchedulerPolicy, cores_per_server: int,
                mem_gb=None):
    """Place one arrival micro-batch. cores/is_uf/p95_eff/valid: (B,)
    arrays (`valid=False` rows are padding and never touch state);
    `rho_cap`: per-chassis admission ceiling — (C,) on chassis
    sum(p95*cores) only (the legacy watt form), or (C, R) over the
    full (watts, cores, GB) resource ledger (+inf disables any axis —
    see `serve.admission`); `mem_gb`: optional (B,) GB demand (None
    places zero GB). Returns (new_state, servers (B,) i32) with
    FAIL_* codes for rejects.

    Arithmetic follows the state dtype: f32 on the serving path, f64
    (bit-equivalent to the numpy rule) when traced under
    `jax.enable_x64(True)` with an f64 state — that is how the
    scheduler simulation's serve backend verifies decision
    equivalence."""
    state, servers, _ = _place_batch_impl(
        state, jnp.inf, cores, is_uf, p95_eff,
        _mem_or_zeros(mem_gb, cores), valid, rho_cap, policy,
        float(cores_per_server))
    return state, servers


class SweepCounters(NamedTuple):
    """In-scan observables of one fused emergency sweep, accumulated in
    the cap-window scan carry (`_apply_cap_windows`) and flushed into
    the host `repro.obs.MetricsRegistry` by the pipeline. All leaves
    are scalars except `cut_by_level_w` (L,) — per-criticality-level
    watts removed, level order = apportionment priority (NUF first)."""
    samples: Any        # i32 — chassis power samples applied
    alarms: Any         # i32 — protective-capping alarms raised
    cut_w: Any          # f — required reduction past the target (W)
    leftover_w: Any     # f — cut no floor absorbed (RAPL trigger, W)
    cut_by_level_w: Any  # (L,) f — realized watts cut per crit level


def _zero_sweep(dtype) -> SweepCounters:
    """All-zero `SweepCounters` (the scan-carry initial value)."""
    return SweepCounters(
        jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
        jnp.zeros((), dtype), jnp.zeros((), dtype),
        jnp.zeros(emergency.N_LEVELS, dtype))


def _apply_cap_windows(ecfg, state: DeviceClusterState, emer, pw, mask,
                       ts):
    """Apply W queued power-emergency sample sub-windows against the
    *current* cluster aggregates, inside whatever jit this is traced
    into. pw/mask/ts: (W, C) dense `masked_step` operands in merged
    order. The windows were all merged *before* the arrival batch this
    rides with, and a cap touches only the emergency state (never the
    placement aggregates), so applying them back-to-back ahead of the
    placement scan is exactly the semantics of dispatching each window
    on its own — minus W extra dispatches. Returns
    ``(emergency_state, SweepCounters)``."""
    rho_lv = emergency.chassis_rho_levels(
        state.gamma_nuf, state.gamma_uf, state.chassis_servers, jnp)
    dtype = state.free_cores.dtype

    def body(carry, xs):
        em, acc = carry
        p, m, t = xs
        em2, out = emergency.masked_step(ecfg, em, rho_lv, p, m, t, jnp)
        acc2 = SweepCounters(
            acc.samples + m.sum(dtype=jnp.int32),
            acc.alarms + out.alarm.sum(dtype=jnp.int32),
            acc.cut_w + out.cut_w.sum(dtype=dtype),
            acc.leftover_w + out.leftover_w.sum(dtype=dtype),
            acc.cut_by_level_w + out.cut_by_level_w.sum(0, dtype=dtype))
        return (em2, acc2), None

    (emer, sweep), _ = jax.lax.scan(body, (emer, _zero_sweep(dtype)),
                                    (pw, mask, ts))
    return emer, sweep


@partial(jax.jit,
         static_argnames=("policy", "cores_per_server", "ecfg"))
def place_batch_caps(state: DeviceClusterState, emer, pw, mask, ts,
                     cores, is_uf, p95_eff, valid, rho_cap,
                     policy: SchedulerPolicy, cores_per_server: int,
                     ecfg, mem_gb=None):
    """`place_batch` with the pending power-emergency cap sub-windows
    fused in front of the placement scan: one compiled dispatch steps
    the emergency state through every queued (W, C) sample window
    (`_apply_cap_windows`) and then places the arrival batch — an
    emergency sweep costs zero extra dispatches on the serving path.
    `ecfg` is the static `emergency.EmergencyConfig`. Returns
    ``(new_state, servers, emergency_state, SweepCounters)`` — the
    sweep counters replace PR 6's scalar alarm count (alarms is now
    ``sweep.alarms``) and feed the observability plane at zero extra
    dispatch cost."""
    emer, sweep = _apply_cap_windows(ecfg, state, emer, pw, mask, ts)
    state, servers, _ = _place_batch_impl(
        state, jnp.inf, cores, is_uf, p95_eff,
        _mem_or_zeros(mem_gb, cores), valid, rho_cap, policy,
        float(cores_per_server))
    return state, servers, emer, sweep


@partial(jax.jit, static_argnames=("policy", "cores_per_server"))
def place_batch_pooled(state: DeviceClusterState, pool, cores, is_uf,
                       p95_eff, valid, rho_cap,
                       policy: SchedulerPolicy, cores_per_server: int,
                       mem_gb=None):
    """`place_batch` with an explicit token pool: each admission
    additionally requires its (R,) demand vector to clear the pool on
    every axis and draws the pool down, else returns FAIL_TOKENS.
    `pool` is a scalar rho-unit balance (the legacy watt protocol) or
    an (R,) (watts, cores, GB) balance. This is the per-shard reserve
    primitive of the sharded serve protocol (`serve.sharding`,
    docs/sharding.md). Returns (new_state, servers, pool_left) with
    pool_left (R,)."""
    return _place_batch_impl(state, pool, cores, is_uf, p95_eff,
                             _mem_or_zeros(mem_gb, cores), valid,
                             rho_cap, policy, float(cores_per_server))


@jax.jit
def remove_batch(state: DeviceClusterState, servers: jnp.ndarray,
                 cores: jnp.ndarray, p95_eff: jnp.ndarray,
                 is_uf: jnp.ndarray, mem_gb=None) -> DeviceClusterState:
    """Batch departure: order-independent scatter-subtract (twin of
    `ClusterState.remove`), crediting the full (R,) demand vector back
    to the ledger. `servers < 0` rows are ignored; negated-cores rows
    are the pinned-placement encoding (`serve.mitigation`) and *debit*
    instead. Follows the state dtype like `place_batch`, so an f64
    place/remove roundtrip is bit-exact."""
    dtype = state.free_cores.dtype
    live = servers >= 0
    srv = jnp.where(live, servers, 0).astype(jnp.int32)
    scale = live.astype(dtype)
    cores = cores.astype(dtype) * scale
    mem = _mem_or_zeros(mem_gb, cores).astype(dtype) * scale
    w = p95_eff.astype(dtype) * cores
    uf_f = is_uf.astype(dtype)
    ch = state.chassis_of[srv]
    d = jnp.stack([w, cores, mem], axis=-1)                 # (B, R)
    return state._replace(
        free_cores=state.free_cores.at[srv].add(cores),
        gamma_uf=state.gamma_uf.at[srv].add(-w * uf_f),
        gamma_nuf=state.gamma_nuf.at[srv].add(-w * (1.0 - uf_f)),
        res_peak=state.res_peak.at[ch].add(-d),
        mem_nuf=state.mem_nuf.at[ch].add(-mem * (1.0 - uf_f)))


def outcome_counters(servers, valid, cores, p95_eff,
                     mem_gb=None) -> dict:
    """Per-batch decision counts from a placement's outputs — the
    host-side (numpy) reduction the observability plane accumulates.

    servers: (B,) outcome codes as returned by the `place_batch`
    family; valid/cores/p95_eff: the matching batch operands. Padding
    rows (``valid=False``) can carry arbitrary codes without ever
    touching state, so every count masks with `valid`. Returns integer
    counts per outcome plus ``rho_admitted`` / ``cores_admitted`` /
    ``gb_admitted`` (the admitted (R,) demand per axis — the exact
    quantities drawn from the chassis `res_peak` ledger and, sharded,
    the token pools; ``mem_gb=None`` reports 0 GB). Keys: admits /
    fail_capacity / fail_power / fail_tokens / rho_admitted /
    cores_admitted / gb_admitted; the first four always sum to
    ``valid.sum()``."""
    servers = np.asarray(servers)
    valid = np.asarray(valid, bool)
    admitted = (servers >= 0) & valid
    cores = np.asarray(cores, np.float64)
    w = np.asarray(p95_eff, np.float64) * cores
    mem = np.zeros_like(cores) if mem_gb is None \
        else np.asarray(mem_gb, np.float64)
    return {
        "admits": int(admitted.sum()),
        "fail_capacity": int(((servers == FAIL_CAPACITY) & valid).sum()),
        "fail_power": int(((servers == FAIL_POWER) & valid).sum()),
        "fail_tokens": int(((servers == FAIL_TOKENS) & valid).sum()),
        "rho_admitted": float(w[admitted].sum()),
        "cores_admitted": float(cores[admitted].sum()),
        "gb_admitted": float(mem[admitted].sum()),
    }
