"""Plain reference of the campus capping simulator: one chassis, one
200 ms poll at a time (paper §III-D).

Each poll: the chassis manager compares the chassis draw with its
alert level; on an alert every batch core drops to the NUF floor, then
four cores per poll move down (over the target) or up (under it, if
the raised setting stays 2 W under the target); the cap lifts 30 s
after the alert clears; a server whose draw stays over its share of
the budget is throttled on all cores by RAPL, 5 % of f_max per poll,
and restored the same way. Server draw is the paper-calibrated model
P_idle(mean f) + sum_c u_c * p_dyn * g(f_c). Interactive cores run at
min(1, load / f), batch cores at 1.

It imports nothing of the program. `r` rounds every intermediate: to
float32, the precision the simulator states, or to bfloat16 for the
control.
"""
from __future__ import annotations

import numpy as np

F_MAX, F_MIN, N_PSTATES = 1.0, 0.5, 11
P_IDLE_FMAX, P_IDLE_FMIN, P_PEAK_FMAX, P_PEAK_FMIN = 112.0, 111.0, 310.0, \
    169.0
CORES = 40
CUBIC_MIX = (0.5 - (P_PEAK_FMIN - P_IDLE_FMIN) / (P_PEAK_FMAX - P_IDLE_FMAX)) \
    / (0.5 - 0.125)
P_DYN = (P_PEAK_FMAX - P_IDLE_FMAX) / CORES
DT, N_RAISE, MARGIN_W, LIFT_S = 0.2, 4, 5.0, 30.0
RAPL_STEP, RAISE_HEADROOM_W, PSU_TRIP_W, ALERT_FRACTION = 0.05 * F_MAX, 2.0, \
    2.0, 0.97
FREQ = np.linspace(F_MAX, F_MIN, N_PSTATES).astype(np.float32)
f32 = np.float32


class Chassis:
    """Layout of one chassis: `uf_vm[s, c]` is the interactive VM that
    owns core c of server s (-1: none), `nuf[s, c]` marks batch cores."""

    def __init__(self, servers: list):
        n = len(servers)
        self.uf_vm = np.full((n, CORES), -1)
        self.nuf = np.zeros((n, CORES), bool)
        self.loads = []
        for s, vms in enumerate(servers):
            c0 = 0
            for v in vms:
                if v["uf"]:
                    self.uf_vm[s, c0:c0 + v["cores"]] = len(self.loads)
                    self.loads.append(v["load"])
                else:
                    self.nuf[s, c0:c0 + v["cores"]] = True
                c0 += v["cores"]
        self.uf = self.uf_vm >= 0
        self.loads = np.asarray(self.loads, np.float32)


def server_power(util, freq, r):
    fr = r(freq * f32(1.0 / F_MAX))
    g = r(r(r(r(f32(CUBIC_MIX) * fr) * fr) * fr) + r(f32(1.0 - CUBIC_MIX)
                                                      * fr))
    dyn = r(r(r(util * g).sum(-1)) * f32(P_DYN))
    idle = r(f32(P_IDLE_FMIN) + r(f32(P_IDLE_FMAX - P_IDLE_FMIN)
                                  * r(f32(2.0) * r(fr.mean(-1)) - f32(1.0))))
    return r(idle + dyn)


def first_n(eligible, level, n):
    """Per server, the `n` eligible cores first by (level, core)."""
    key = np.where(eligible, level * (CORES + 1) + np.arange(CORES), 1 << 30)
    order = np.argsort(key, axis=-1, kind="stable")[:, :n]
    sel = np.zeros_like(eligible)
    rows = np.arange(len(key))[:, None]
    sel[rows, order] = np.take_along_axis(key, order, -1) < (1 << 30)
    return sel


def simulate(ch: Chassis, budget_w: float, traces: np.ndarray, r) -> np.ndarray:
    """(steps,) chassis draw after each poll's control action."""
    n_srv = ch.uf.shape[0]
    server_b = r(f32(budget_w) / f32(n_srv))
    target = r(server_b - f32(MARGIN_W))
    alert_w = r(f32(budget_w) * f32(ALERT_FRACTION))
    floor = N_PSTATES - 1
    freq = np.full((n_srv, CORES), f32(F_MAX))
    pstate = np.zeros((n_srv, CORES), np.int64)
    capping = np.zeros(n_srv, bool)
    rapl = np.zeros(n_srv, bool)
    clear_s = np.full(n_srv, np.inf, np.float32)
    out = np.zeros(len(traces), np.float32)
    low = ~ch.uf
    for t, load in enumerate(traces):
        lc = np.where(ch.uf, load[np.maximum(ch.uf_vm, 0)], f32(0))
        util = np.where(ch.uf, np.minimum(
            r(lc / np.maximum(freq, f32(1e-3))), f32(1.0)), f32(0.0))
        util = np.where(ch.nuf, f32(1.0), util)
        p0 = server_power(util, freq, r)
        alert = r(p0.sum()) >= alert_w
        # in-band per-VM controller
        over = p0 > target
        start = alert & over & ~capping
        quiet = ~(alert | over)
        clear = np.where(capping & quiet, r(clear_s + f32(DT)), f32(0.0))
        lift = capping & (clear >= f32(LIFT_S))
        lower = capping & ~lift & over
        raise_ = capping & ~lift & ~over
        elig = low & np.where(lower[:, None], pstate < floor,
                              raise_[:, None] & (pstate > 0))
        level = np.where(lower[:, None], pstate, N_PSTATES - 1 - pstate)
        sel = first_n(elig, level, N_RAISE)
        trial = pstate - (sel & raise_[:, None])
        commit = raise_ & (server_power(util, FREQ[trial], r)
                           < r(target - f32(RAISE_HEADROOM_W)))
        ps = np.where(start[:, None] & low, floor, pstate)
        ps = np.where(lift[:, None], 0, ps)
        ps = ps + (sel & lower[:, None])
        ps = np.where(commit[:, None], trial, ps)
        capping = (capping | start) & ~lift
        rapl = rapl & ~lift
        clear_s = np.where(start, f32(0.0),
                           np.where(capping, clear, f32(np.inf)))
        intended = FREQ[ps]
        freq = np.where(rapl[:, None], np.minimum(intended, freq), intended)
        pstate = ps
        p1 = server_power(util, freq, r)
        # out-of-band RAPL
        engaged = (p1 > r(server_b + f32(PSU_TRIP_W))) | rapl
        over_b = p1 > server_b
        cut = engaged & over_b
        restore = engaged & ~over_b & rapl
        uniform = np.maximum(r(freq.max(-1) - f32(RAPL_STEP)), f32(F_MIN))
        f2 = np.where(cut[:, None], np.minimum(freq, uniform[:, None]), freq)
        up = restore & (p1 < r(server_b - f32(2.0 * MARGIN_W)))
        f2 = np.where(up[:, None], np.minimum(r(f2 + f32(RAPL_STEP)),
                                              intended), f2)
        done = (f2 >= r(intended - f32(1e-9))).all(-1)
        rapl = np.where(cut, True, np.where(restore & done, False, rapl))
        freq = f2
        out[t] = r(server_power(util, freq, r).sum())
    return out


def rounding(precision: str):
    if precision == "float32":
        return lambda x: np.asarray(x, np.float32)
    from ml_dtypes import bfloat16
    return lambda x: np.asarray(np.asarray(x, np.float32).astype(bfloat16),
                                np.float32)
