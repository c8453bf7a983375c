"""Plain reference of the admission path: features, forests, gating,
and the paper's rule-aggregated placement (Algorithm 1 with the
packing rule, §II-C) under per-chassis power admission.

It imports nothing of the program. Every step is the straightforward
form: per-subscription means from sums, a loop over trees, two stable
argsorts per arrival. `dtype` is the arithmetic of the scoring and
prediction steps: float32 is the precision the serving path states;
`bf16` rounds every intermediate to bfloat16 and is the control, which
must fail the comparison.
"""
from __future__ import annotations

import numpy as np

try:                                   # shipped with jax
    from ml_dtypes import bfloat16
except ImportError:                    # pragma: no cover
    bfloat16 = None

N_BUCKETS = 4
N_VM_TYPES = 7
CONF_GATE = 0.6
UF, NUF = 1, 0
FAIL_CAPACITY, FAIL_POWER = -1, -2
#: paper §IV-A blade: 112 W idle and 310 W busy at nominal frequency
IDLE_W, PEAK_W, CORES_PER_BLADE = 112.0, 310.0, 40
#: features of a subscription the history never saw
DEFAULT_AGG = np.array([0.5, 0.2, 0.0, 0.25, 0.25, 0.25, 0.25, 30.0, 50.0])


class Arith:
    """Rounding of one precision: `r(x)` rounds an array to it."""

    def __init__(self, name: str):
        self.name = name
        if name == "float32":
            self.r = lambda x: np.asarray(x, np.float32)
        elif name == "bf16":
            self.r = lambda x: np.asarray(
                np.asarray(x, np.float32).astype(bfloat16), np.float32)
        else:
            raise ValueError(f"unknown precision {name!r}")


def p95_bucket(p95_util) -> np.ndarray:
    """Paper buckets 0-25, 26-50, 51-75, 76-100 (whole percents)."""
    return np.clip(np.ceil(np.asarray(p95_util, np.float64) / 25.0) - 1, 0,
                   N_BUCKETS - 1).astype(np.int64)


class SubscriptionSums:
    """Per-subscription sums over a labelled history."""

    def __init__(self, capacity, subscription, uf, lifetime_h, p95, avg):
        def acc(w):
            out = np.zeros(capacity)
            np.add.at(out, subscription, w)
            return out
        self.count = acc(np.ones(len(subscription)))
        self.uf = acc(np.asarray(uf, np.float64))
        self.lived7d = acc(np.asarray(lifetime_h) >= 168)
        self.bucket = np.zeros((capacity, N_BUCKETS))
        np.add.at(self.bucket, (subscription, p95_bucket(p95)), 1.0)
        self.avg = acc(np.asarray(avg, np.float64))
        self.p95 = acc(np.asarray(p95, np.float64))

    def features(self, subscription, cores, memory_gb, vm_type, ar=None):
        """(B, 18) features: nine subscription means (defaults where
        unseen), cores, memory, one-hot VM type — stored as float32,
        or rounded by `ar`."""
        sub = np.asarray(subscription)
        cnt = self.count[sub]
        d = np.maximum(cnt, 1.0)
        agg = np.column_stack([self.uf[sub] / d, self.lived7d[sub] / d, cnt,
                               self.bucket[sub] / d[:, None],
                               self.avg[sub] / d, self.p95[sub] / d])
        agg = np.where((cnt > 0)[:, None], agg, DEFAULT_AGG[None])
        x = np.column_stack([agg, cores, memory_gb,
                             np.eye(N_VM_TYPES)[np.asarray(vm_type)]])
        return (ar.r if ar is not None else Arith("float32").r)(x)


def forest_proba(forest: dict, x: np.ndarray, ar: Arith) -> np.ndarray:
    """(B, n_out) mean of the trees' leaf probabilities. `forest` holds
    `feat_idx` (T, D), `thresholds` (T, D) and `leaf_values` (T, 2^D,
    n_out); a tree goes right at depth d iff ``x[f] > threshold``."""
    fi, th, lv = forest["feat_idx"], ar.r(forest["thresholds"]), \
        ar.r(forest["leaf_values"])
    n_trees, depth = fi.shape
    acc = np.zeros((x.shape[0], lv.shape[-1]), np.float32)
    for tr in range(n_trees):
        leaf = np.zeros(x.shape[0], np.int64)
        for d in range(depth):
            leaf = leaf * 2 + (x[:, fi[tr, d]] > th[tr, d])
        acc = ar.r(acc + lv[tr, leaf])
    return ar.r(acc / np.float32(n_trees))


def predict(forest: dict, x, ar: Arith):
    p = forest_proba(forest, x, ar)
    return p.argmax(-1), p.max(-1)


def query(service: dict, x, ar: Arith) -> dict:
    """The two-stage service with its confidence gate: criticality, and
    P95 bucket from stage 1 (over 50 %?) then the low or high forest.
    Low-confidence heads fall back to user-facing and bucket 3."""
    wt, wt_conf = predict(service["criticality"], x, ar)
    s1, c1 = predict(service["stage1"], x, ar)
    lo_b, lo_c = predict(service["low"], x, ar)
    hi_b, hi_c = predict(service["high"], x, ar)
    bucket = np.where(s1 == 1, hi_b + 2, lo_b)
    conf = np.minimum(c1, np.where(s1 == 1, hi_c, lo_c))
    gate = ar.r(CONF_GATE)
    return {"workload_type": np.where(wt_conf >= gate, wt, UF),
            "p95_bucket": np.where(conf >= gate, bucket, 3),
            "workload_conf": wt_conf, "p95_conf": conf}


def bucket_to_p95(bucket) -> np.ndarray:
    """Bucket midpoint as a fraction: 0.125, 0.375, 0.625, 0.875."""
    return ((np.asarray(bucket) * 25.0 + 12.5) / 100.0).astype(np.float32)


class Ledger:
    """Cluster aggregates, kept exactly (float64; every demand is a
    multiple of 1/8 core, so float32 state must agree to the bit)."""

    def __init__(self, n_servers, cores_per_server, blades):
        self.cps = float(cores_per_server)
        self.chassis_of = np.arange(n_servers) // blades
        n_chassis = n_servers // blades
        self.free = np.full(n_servers, self.cps)
        self.g_uf = np.zeros(n_servers)
        self.g_nuf = np.zeros(n_servers)
        self.res = np.zeros((n_chassis, 3))        # (rho, cores, GB)
        self.mem_nuf = np.zeros(n_chassis)
        self.rho_max = np.bincount(self.chassis_of) * self.cps

    def apply(self, server, cores, p95, is_uf, mem, sign=1.0):
        """Add (sign=1) or remove (sign=-1) VMs, in any order."""
        server = np.asarray(server)
        ok = server >= 0
        s, c = server[ok], np.asarray(cores, np.float64)[ok] * sign
        w = np.asarray(p95, np.float64)[ok] * c
        uf = np.asarray(is_uf, bool)[ok]
        m = np.asarray(mem, np.float64)[ok] * sign
        ch = self.chassis_of[s]
        np.add.at(self.free, s, -c)
        np.add.at(self.g_uf, s[uf], w[uf])
        np.add.at(self.g_nuf, s[~uf], w[~uf])
        np.add.at(self.res, ch, np.column_stack([w, c, m]))
        np.add.at(self.mem_nuf, ch[~uf], m[~uf])

    def apply_one(self, s: int, c: float, p95: float, uf: bool, m: float):
        """Add one VM."""
        ch = self.chassis_of[s]
        w = p95 * c
        self.free[s] -= c
        if uf:
            self.g_uf[s] += w
        else:
            self.g_nuf[s] += w
            self.mem_nuf[ch] += m
        self.res[ch] += (w, c, m)

    def rho_levels(self) -> np.ndarray:
        """(C, 2) committed p95*cores per chassis: NUF, UF."""
        n_chassis = len(self.rho_max)
        return np.column_stack([
            np.bincount(self.chassis_of, self.g_nuf, n_chassis),
            np.bincount(self.chassis_of, self.g_uf, n_chassis)])


def rank_weight(scores: np.ndarray, ar: Arith) -> np.ndarray:
    """Best candidate 1, worst 0, by stable descending order."""
    n = len(scores)
    if n == 1:
        return np.ones(1, np.float32)
    # descending score, ties to the lower index: one sort of unique
    # keys (the order-preserving integer image of -score, then index)
    u = (-scores + np.float32(0.0)).astype(np.float32).view(np.uint32)
    key = (u ^ np.where(u >> 31, np.uint32(0xFFFFFFFF),
                        np.uint32(0x80000000))).astype(np.uint64)
    order = np.empty(n, np.float32)
    order[np.argsort((key << np.uint64(32)) | np.arange(n, dtype=np.uint64))] \
        = np.arange(n)
    return ar.r(1.0 - ar.r(order / np.float32(n - 1)))


def choose(led: Ledger, cores, is_uf, policy: dict, ar: Arith) -> int:
    """The winning server (or FAIL_CAPACITY): the candidates with enough
    free cores, each weighted by its rank under the packing rule (fuller
    first) and under the power rule (alpha * chassis score + (1 - alpha)
    * server score), the first maximum by server index."""
    cand = np.nonzero(led.free >= cores)[0]
    if len(cand) == 0:
        return FAIL_CAPACITY
    f32 = np.float32
    cps = f32(led.cps)
    pack = ar.r(1.0 - ar.r(led.free[cand].astype(f32) / cps))
    rho_peak = led.res[:, 0].astype(f32)
    kappa = ar.r(1.0 - ar.r(rho_peak / np.maximum(led.rho_max, 1e-9)
                            .astype(f32)))
    diff = (led.g_nuf - led.g_uf) if is_uf else (led.g_uf - led.g_nuf)
    eta = ar.r(f32(0.5) * ar.r(1.0 + ar.r(diff.astype(f32) / cps)))
    a = f32(policy["alpha"])
    power = ar.r(ar.r(a * kappa[led.chassis_of[cand]])
                 + ar.r(f32(1.0 - policy["alpha"]) * eta[cand]))
    obj = ar.r(ar.r(f32(policy["packing_weight"]) * rank_weight(pack, ar))
               + ar.r(f32(policy["power_weight"]) * rank_weight(power, ar)))
    return int(cand[int(np.argmax(obj))])


def admit(led: Ledger, server, cores, p95, mem, caps, ar: Arith) -> bool:
    """Every axis of the chassis ledger stays within its ceiling."""
    ch = led.chassis_of[server]
    d = np.array([ar.r(np.float32(p95) * np.float32(cores)), cores, mem],
                 np.float32)
    return bool(np.all(ar.r(led.res[ch].astype(np.float32) + d) <= caps[ch]))


def rho_cap(budget_w, blades, idle_w, p_dyn_per_core, n_chassis):
    """(C,) float32 ceiling on chassis p95*cores from a watt budget:
    (budget - blades * idle) / dynamic watts per core."""
    cap = max((budget_w - blades * idle_w) / p_dyn_per_core, 0.0)
    return np.full(n_chassis, cap, np.float32)


class Adaptive:
    """The closed-loop ratio controller of the serve path, plainly: a
    ring of the last `window` utilizations per chassis (read back from
    power samples), a chassis is stable when it has `min_history`
    samples, a low/high percentile spread and a sign-flip rate under
    their thresholds and its latest sample is not hot; the ratio steps
    up when enough known chassis are stable and none is hot, and down
    when any is hot or too few are stable."""

    def __init__(self, cfg: dict, n_chassis: int):
        self.cfg = cfg
        self.util = [[] for _ in range(n_chassis)]
        self.ratio = np.float32(1.0)

    def step(self, rho_lv, chassis, power_w):
        c, f32 = self.cfg, np.float32
        rho = rho_lv.sum(-1).astype(f32)
        for ch, pw in zip(chassis, power_w):
            dyn = max(f32(pw) - f32(c["static_w"]), f32(0.0))
            u = f32(dyn / f32(f32(c["p_dyn_per_core"]) * rho[ch])) \
                if rho[ch] > 0 else f32(0.0)
            self.util[ch] = (self.util[ch] + [u])[-c["window"]:]
        n_known = n_stable = hot = 0
        for w in self.util:
            if not w:
                continue
            is_hot = w[-1] > f32(c["hot_util"])
            hot |= is_hot
            if len(w) < c["min_history"]:
                continue
            n_known += 1
            s = sorted(w)
            nm1 = f32(len(w) - 1)
            spread = s[int(f32(c["spread_q_hi"]) * nm1)] \
                - s[int(f32(c["spread_q_lo"]) * nm1)]
            d = np.diff(np.asarray(w, f32))
            flips = int(((np.sign(d[1:]) * np.sign(d[:-1])) < 0).sum())
            flip_rate = f32(flips) / f32(max(len(w) - 2, 1))
            n_stable += bool(spread <= f32(c["spread_thresh"])
                             and flip_rate <= f32(c["flip_thresh"])
                             and not is_hot)
        frac = f32(n_stable) / f32(max(n_known, 1))
        up = n_known > 0 and not hot and frac >= f32(c["ratchet_quorum"])
        down = hot or (n_known > 0 and frac < f32(c["backoff_quorum"]))
        self.ratio = np.clip(
            f32(self.ratio + f32(c["step_up"]) * f32(up)
                - f32(c["step_down"]) * f32(down)),
            f32(c["ratio_min"]), f32(c["ratio_max"]))


#: p-states f_max .. f_min in 11 steps; g(f) the calibrated dynamic
#: power multiplier (paper §IV-A: 310 W busy at f_max, 169 W at f_max/2)
FREQ = np.linspace(1.0, 0.5, 11).astype(np.float32).astype(np.float64)
_MIX = (0.5 - (169.0 - 111.0) / (PEAK_W - IDLE_W)) / (0.5 - 0.125)
REDUCIBLE = 1.0 - (_MIX * FREQ ** 3 + (1.0 - _MIX) * FREQ)


class Emergency:
    """The emergency plane with its ballooning rung, one sample window
    at a time, in float32: each sampled chassis first credits its
    standing balloon, then (on an alarm) balloons NUF memory out for
    the part of the cut the NUF frequency floor cannot absorb; the
    power left alarms at 97 % of the budget, and the cut down to
    budget - 5 W is taken from NUF draw to its floor first, then from
    UF draw to its floor; what neither absorbs engages RAPL. A cleared
    chassis holds its caps 30 s, then lifts them.

    `step` also gives every outcome the plane's own float32 rounding
    can reach: a decision whose input lies within `delta_w` watts
    (`delta_s` seconds) of its threshold may go either way, and the
    compared state has to equal one of the outcomes. Where the cut
    reaches the UF level, RAPL's ``leftover > TOL`` compares a
    difference of near-equal watts with a tolerance below float32
    resolution, so either side is reachable there."""

    TOL = 1e-6
    FLOORS = (10, 5)                 # deepest p-state: NUF, UF
    #: 8 float32 ulps of a chassis draw near 2 kW; 5 ulps of 30 s
    DELTA_W, DELTA_S = 2e-3, 1e-5

    def __init__(self, budget_w, blades, ar: Arith, w_per_gb=0.375,
                 reclaim_frac=0.5, delta_w=DELTA_W, delta_s=DELTA_S):
        f = np.float32
        self.r = ar.r
        self.static = f(blades * IDLE_W)
        self.p_dyn = f((PEAK_W - IDLE_W) / CORES_PER_BLADE)
        self.alert, self.target = f(budget_w * 0.97), f(budget_w - 5.0)
        self.w_gb, self.reclaim = f(w_per_gb), f(reclaim_frac)
        self.fracs = REDUCIBLE.astype(np.float32)
        self.dw, self.ds = f(delta_w), f(delta_s)

    @staticmethod
    def init(n_chassis) -> dict:
        return {"pstate": np.zeros((n_chassis, 2), np.int64),
                "rapl": np.zeros(n_chassis, bool),
                "capped_s": np.zeros(n_chassis, np.float32),
                "clear_s": np.full(n_chassis, np.inf, np.float32),
                "last_t": np.full(n_chassis, -np.inf, np.float32),
                "ballooned": np.zeros(n_chassis, np.float32)}

    @staticmethod
    def exactly(st: dict) -> dict:
        """The outcomes of a chassis left as `st` holds it: "pstate"
        (C, 2, 11) and "rapl" (C, 2) masks, "balloon" (C, 2) candidate
        values (NaN: none)."""
        n = len(st["rapl"])
        pm = np.zeros((n, 2, len(REDUCIBLE)), bool)
        pm[np.arange(n)[:, None], np.arange(2), st["pstate"]] = True
        rm = np.zeros((n, 2), bool)
        rm[np.arange(n), st["rapl"].astype(np.int64)] = True
        bal = np.column_stack([st["ballooned"],
                               np.full(n, np.nan, np.float32)])
        return {"pstate": pm, "rapl": rm, "balloon": bal}

    def _util(self, rho_lv, power):
        f = np.float32
        rho = rho_lv.sum(-1, dtype=f)
        dyn = np.maximum(power - self.static, f(0))
        with np.errstate(divide="ignore", invalid="ignore"):
            u = (dyn / (self.p_dyn * np.where(rho > 0, rho, f(1)))).astype(f)
        return np.where(rho > 0, u, f(0))

    def _draw(self, lv, power):
        """(C, 2) dynamic draw per level and (C,) chassis draw."""
        f, r = np.float32, self.r
        dyn_full = r((self.p_dyn * lv) * self._util(lv, power)[:, None])
        return dyn_full, r(self.static + r(dyn_full.sum(-1, dtype=f)))

    def _pstates(self, cut, red_max, dyn_full):
        """(C, 2) p-state per level for a cut taken NUF first."""
        f, r = np.float32, self.r
        take = r(np.clip(cut[:, None] - (np.cumsum(red_max, -1) - red_max),
                         f(0), red_max))
        with np.errstate(divide="ignore", invalid="ignore"):
            ratio = r(np.where(dyn_full > 0, (take / np.where(
                dyn_full > 0, dyn_full, f(1))).astype(f), f(0)))
        return take, np.minimum((self.fracs < ratio[..., None]).sum(-1),
                                self.FLOORS)

    def step(self, st: dict, rho_lv, mem_nuf, chassis, power, ts,
             got: dict | None = None):
        """One window over `chassis` from state `st` (not modified).
        Returns (new state, outcomes): the outcomes (as `exactly` gives
        them) every chassis can reach. Where the balloon's alarm can go
        either way, the branch nearer ``got["ballooned"]`` (the state
        compared after the window) is followed into the capping."""
        f, r, dw = np.float32, self.r, self.dw
        st = {k: v.copy() for k, v in st.items()}
        out = self.exactly(st)
        ch = np.asarray(chassis)
        lv = r(np.asarray(rho_lv)[ch])
        pw, mem = r(power), r(np.asarray(mem_nuf)[ch])
        ts = np.asarray(ts, f)
        # ballooning rung: the alarmed branch and the clear one
        b = st["ballooned"][ch]
        standing = self.w_gb * b
        dyn_full, p_full = self._draw(lv, pw - standing)
        dyn = r(dyn_full.sum(-1, dtype=f))
        alarm = p_full >= self.alert
        may, must = p_full >= self.alert - dw, p_full >= self.alert + dw
        cut = r(np.maximum(p_full - self.target, f(0)))
        cap_nuf = r(dyn_full[:, 0] * f(REDUCIBLE[self.FLOORS[0]]))
        deficit = np.maximum(cut - cap_nuf, f(0))
        denom = np.maximum(dyn - cap_nuf, f(self.TOL))
        demand = np.where(deficit > f(self.TOL),
                          ((deficit + f(self.TOL)) * dyn / denom).astype(f),
                          f(0))
        head = np.maximum(self.reclaim * mem - b, f(0))
        grab = r(np.minimum((demand / self.w_gb).astype(f), head))
        b_on, b_off = b + grab, np.zeros_like(b)
        on = alarm
        if got is not None:
            g = np.asarray(got["ballooned"])[ch]
            on = np.where(may & ~must, np.abs(g - b_on) <= np.abs(g - b_off),
                          alarm)
        out["balloon"][ch] = np.column_stack([np.where(may, b_on, np.nan),
                                              np.where(~must, b_off, np.nan)])
        st["ballooned"][ch] = np.where(on, b_on, b_off)
        p_adj = pw - (standing + self.w_gb * np.where(on, grab, f(0)))
        # capping
        dyn_full, p_full = self._draw(lv, p_adj)
        alarm = p_full >= self.alert
        may, must = p_full >= self.alert - dw, p_full >= self.alert + dw
        last = st["last_t"][ch]
        dt = np.where(np.isfinite(last), np.maximum(ts - last, f(0)), f(0))
        prev_p, prev_r = st["pstate"][ch], st["rapl"][ch]
        was_capped = ((prev_p > 0) | prev_r[:, None]).any(-1)
        capped = (st["capped_s"][ch] + dt) * was_capped
        clear_c = np.where(was_capped, st["clear_s"][ch] + dt, f(np.inf))
        clear = np.where(alarm, f(0), clear_c)
        lift = was_capped & ~alarm & (clear >= f(30.0))
        hold = was_capped & ~alarm & ~lift
        red_max = r(dyn_full * self.fracs[list(self.FLOORS)])
        cut = r(np.maximum(p_full - self.target, f(0)))
        take, pst = self._pstates(cut, red_max, dyn_full)
        leftover = r(np.maximum(cut - take.sum(-1, dtype=f), f(0)))
        pstate = np.where(alarm[:, None], pst,
                          np.where(hold[:, None], prev_p, 0))
        rapl = np.where(alarm, leftover > f(self.TOL),
                        np.where(hold, prev_r, False))
        now = (pstate > 0).any(-1) | rapl
        st["capped_s"][ch] = np.where(now, capped, f(0))
        st["clear_s"][ch] = np.where(alarm, f(0),
                                     np.where(now, clear, f(np.inf)))
        st["pstate"][ch], st["rapl"][ch], st["last_t"][ch] = pstate, rapl, ts
        # every outcome within rounding: the alarmed branch for a cut
        # moved by dw either way, the clear branch held or lifted
        zero = f(0)
        lo = self._pstates(np.maximum(cut - dw, zero), red_max, dyn_full)[1]
        hi = self._pstates(cut + dw, red_max, dyn_full)[1]
        k = np.arange(len(REDUCIBLE))
        uf_part = cut + dw > red_max[:, 0]
        r_true = (leftover > f(self.TOL)) | uf_part
        r_false = (leftover <= f(self.TOL)) \
            | (cut - dw < red_max.sum(-1, dtype=f))
        may_hold = was_capped & (clear_c < f(30.0) + self.ds)
        may_free = ~was_capped | (clear_c >= f(30.0) - self.ds)
        off = ~must
        pm = (may[:, None, None] & (lo[..., None] <= k) & (k <= hi[..., None])) \
            | ((off & may_hold)[:, None, None] & (k == prev_p[..., None])) \
            | ((off & may_free)[:, None, None] & (k == 0))
        rm = np.column_stack([
            (may & r_false) | (off & may_hold & ~prev_r) | (off & may_free),
            (may & r_true) | (off & may_hold & prev_r)])
        out["pstate"][ch], out["rapl"][ch] = pm, rm
        return st, out
