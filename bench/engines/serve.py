"""Serve engine: a Fig-7 cluster behind `ServePipeline`, driven through
`submit_to` / `depart_to` / `cap_to` / `flush` by the stamped stream
of `bench.traffic.generator`, and checked against the plain reference
of `bench.reference.serve_ref`.

One push step sends what is due before the next stamped event, then
the event: departures (one chunk, padded to a fixed size so the
program compiles one departure program per size), then either a
deployment's VMs or one power sample per chassis. Every stamp is
larger than the one pushed before it, on every host, so the merged
order the pipeline serves in is the push order, and the reference
replays that log.
"""
from __future__ import annotations

import heapq
import time

import numpy as np

from bench.reference import serve_ref as R
from bench.traffic import generator as gen
from bench.world import World

#: at most this many micro-batches of a window are decided again by
#: the reference, drawn from the seed (about 30,000 arrivals)
MAX_CHECKED_BATCHES = 120
#: departure chunks are padded (server -1) to the smallest of these
DEPART_SIZES = (16, 64, 256, 1024)


class ServeCell:
    """One serve cell: world, pipeline, stream, push loop and check."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, ann):
        self.cfg, self.traffic, self.ann, self.seed = cfg, traffic, ann, seed
        self.world = World(seed, cfg["history_vms"], cfg["forest"]["n_trees"],
                           cfg["forest"]["depth"])
        rng = np.random.default_rng([seed, 2])
        self.rate = traffic["vm_rate_per_s"]
        n_vms = int(self.rate * traffic["stream_s"])
        self.hosts = cfg["n_ingest_hosts"]
        self.n_servers = cfg["n_chassis"] * cfg["blades_per_chassis"]
        # lifetimes are compressed so that the stream offers, at its
        # rate, this share of the cluster's cores; set-up starts from
        # the stationary population of such a stream, and admission
        # keeps what the budgets allow of it
        offered = traffic["offered_core_share"] * self.n_servers \
            * cfg["cores_per_server"]
        self.compress = offered / (self.rate * gen.MEAN_LIFETIME_H
                                   * gen.MEAN_CORES)
        self.stream = gen.stationary_stream(
            rng, self.world.subs, int(round(offered / gen.MEAN_CORES)),
            traffic["fill_s"], n_vms, self.rate, self.hosts)
        self.power_rng = np.random.default_rng([seed, 3])
        self.pipe = self._build_pipeline()
        n = len(self.stream.vms)
        self.server = np.full(n, -9, np.int32)
        self.wt = np.zeros(n, np.int32)
        self.bucket = np.zeros(n, np.int32)
        self.p95_eff = np.zeros(n, np.float32)
        self.t_ret = np.zeros(n)
        self.n_decided = 0
        self.n_pushed = 0
        self.n_results = 0
        self.d = 0                       # next deployment
        self.k_cap = 1                   # next power sweep, in periods
        self.t_last = -np.inf
        self.due = []                    # heap of (stamp, vm)
        self.log = []                    # pushed events, merged order
        self.caps = []                   # plane state after each sweep
        self.sweep_t = []                # last stamp of each sweep
        self.longest = (0.0, None)       # slowest push of the window
        #: padded sizes still to be sent once, so that every departure
        #: program compiles during the warm-up
        self.force = list(DEPART_SIZES)

    def _build_pipeline(self):
        from repro.core.placement import SchedulerPolicy
        from repro.obs import Observability
        from repro.serve import (
            AdaptiveConfig, BallooningConfig, EmergencyConfig, PlaneBundle,
            ResourceVector, ServeConfig, ServePipeline)
        from repro.serve.placement import fresh_state
        cfg = self.cfg
        blades, budget = cfg["blades_per_chassis"], cfg["chassis_budget_w"]
        planes = cfg["planes"]
        bundle = PlaneBundle(
            chassis_budget=ResourceVector(watts=budget),
            emergency=EmergencyConfig.from_model(
                budget, blades_per_chassis=blades)
            if planes["emergency"] else None,
            ballooning=BallooningConfig() if planes["ballooning"] else None,
            adaptive=AdaptiveConfig(blades_per_chassis=blades)
            if planes["adaptive"] else None,
            obs=Observability.full() if planes["obs"] == "full" else None)
        state = fresh_state(self.n_servers, cfg["cores_per_server"],
                            np.arange(self.n_servers) // blades)
        config = ServeConfig(
            batch_size=cfg["batch_size"], policy=SchedulerPolicy(
                **cfg["policy"]),
            n_ingest_hosts=self.hosts, planes=bundle)
        return ServePipeline(self.world.program_service(),
                             self.world.program_table(), state,
                             cfg["cores_per_server"], config=config,
                             blades_per_chassis=blades)

    # -- the push loop -----------------------------------------------------
    def _take(self, results, t_ret: float) -> None:
        """Record what a push call returned (and the plane state after
        any power sweep it applied; references only, nothing is read
        from the device here)."""
        self._snap()
        for r in results:
            n0, n = self.n_decided, len(r.server)
            sl = slice(n0, n0 + n)
            self.server[sl] = r.server
            self.wt[sl] = r.workload_type
            self.bucket[sl] = r.p95_bucket
            self.p95_eff[sl] = r.p95_eff
            self.t_ret[sl] = t_ret
            self.n_decided += n
            self.n_results += 1
            st = self.stream
            for i in np.nonzero(r.server >= 0)[0] + n0:
                heapq.heappush(self.due, (
                    st.t[i] + self.compress * st.depart_h[i], i))

    def _snap(self, flushed: bool = False) -> None:
        """Keep a reference to the plane's state after each power sweep
        the last call applied: a sweep applies once the ingest watermark
        passes its stamps (all of them at a flush)."""
        wm = np.inf if flushed else self.pipe.ingest.watermark
        while len(self.caps) < len(self.sweep_t) \
                and self.sweep_t[len(self.caps)] <= wm:
            self.caps.append((self.pipe.emergency, self.pipe.balloon_state))

    def _next_stamp(self):
        t_dep = self.stream.t[self.stream.start[self.d]]
        t_cap = self.k_cap * self.traffic["power_period_s"]
        return (t_cap, True) if t_cap < t_dep else (t_dep, False)

    def _push_departures(self, host: int, before: float) -> None:
        """One chunk of the departures due before `before`, stamped
        after everything pushed so far (a departure found late is
        stamped late), padded with ignored rows to a fixed size."""
        go = []
        while self.due and self.due[0][0] < before \
                and len(go) < DEPART_SIZES[-1]:
            go.append(heapq.heappop(self.due))
        forced = self.force.pop(0) if self.force else 0
        if not go and not forced:
            return
        lo = self.t_last if np.isfinite(self.t_last) else 0.0
        stamps = np.array([g[0] for g in go], np.float64)
        vm = np.array([g[1] for g in go], np.int64)
        late = int((stamps <= lo).sum())
        top = stamps[late] if late < len(go) else before
        stamps[:late] = lo + (top - lo) * np.arange(1, late + 1) / (late + 1)
        size = next(s for s in DEPART_SIZES if s >= max(len(go), forced))
        vms = self.stream.vms

        def padded(a, fill):
            return np.concatenate([a, np.full(size - len(a), fill, a.dtype)])
        server = padded(self.server[vm], -1)
        cores = padded(vms.cores[vm], 0)
        p95 = padded(self.p95_eff[vm], 0)
        is_uf = padded(self.wt[vm] == R.UF, False)
        mem = padded(vms.memory_gb[vm], 0)
        last = stamps[-1] if len(go) else 0.5 * (lo + before)
        stamps = padded(stamps, last)
        self.log.append(("D", server, cores, p95, is_uf, mem))
        with self.ann("bench:depart_to"):
            res = self.pipe.depart_to(host, server, cores, p95, is_uf,
                                      t=stamps, mem_gb=mem)
        self.t_last = stamps[-1]
        self._take(res, time.perf_counter())

    def step(self):
        """Push what is due before the next event, then the event.
        Returns the deployment pushed, or None for a power sweep."""
        from repro.sim.telemetry import ArrivalBatch
        stamp, is_cap = self._next_stamp()
        if is_cap:
            host = self.k_cap % self.hosts
            nxt = self.stream.t[self.stream.start[self.d]]
            if stamp <= self.t_last:
                # the deployment before straddles the sweep's time: the
                # sweep goes right after it
                stamp = self.t_last + min(1e-7, 0.25 * (nxt - self.t_last))
            self._push_departures(host, stamp)
            n_ch = self.cfg["n_chassis"]
            t = stamp + np.linspace(0.0, min(1e-5, 0.5 * (nxt - stamp)),
                                    n_ch)
            power = gen.power_samples(self.power_rng, n_ch,
                                      self.cfg["chassis_budget_w"],
                                      self.traffic["power_band"])
            chassis = np.arange(n_ch)
            self.log.append(("C", chassis, power, t, self.k_cap - 1))
            self.sweep_t.append(t[-1])
            with self.ann("bench:cap_to"):
                res = self.pipe.cap_to(host, chassis, power, t=t)
            self.t_last = t[-1]
            self.k_cap += 1
            self._take(res, time.perf_counter())
            return None
        d = self.d
        host = int(self.stream.host[d])
        self._push_departures(host, stamp)
        lo, hi = self.stream.start[d], self.stream.start[d + 1]
        v = self.stream.vms
        batch = ArrivalBatch(v.subscription[lo:hi], v.cores[lo:hi],
                             v.memory_gb[lo:hi], v.vm_type[lo:hi],
                             v.user_facing[lo:hi], v.p95_util[lo:hi],
                             v.lifetime_h[lo:hi])
        self.log.append(("A", lo, hi))
        with self.ann("bench:submit_to"):
            res = self.pipe.submit_to(host, batch, t=self.stream.t[lo:hi])
        self.t_last = self.stream.t[hi - 1]
        self.d += 1
        self.n_pushed = hi
        self._take(res, time.perf_counter())
        if self.d + 1 >= self.stream.n_deploy:
            raise RuntimeError("the generated stream ran out; raise "
                               "stream_s in the traffic file")
        return d

    # -- phases ------------------------------------------------------------
    def warm_up(self) -> dict:
        """Serve the stream back to back for `warmup_stream_s` of stream
        time: the fill, then the stream at its rate, with one empty
        departure chunk of every padded size pushed so that each
        departure program is compiled before the window."""
        end = self.traffic["warmup_stream_s"]
        while self._next_stamp()[0] < end:
            self.step()
        return {"lifetime_s_per_h": round(self.compress, 6),
                "occupancy_cores": self.occupancy()}

    def occupancy(self) -> float:
        free = np.asarray(self.pipe.state.free_cores, np.float64)
        return float(self.n_servers * self.cfg["cores_per_server"]
                     - free.sum())

    def window(self, seconds: float) -> dict:
        """Measure for `seconds` of wall time, back to back or open loop
        at the stamps."""
        paced = self.traffic["pacing"] == "open_loop"
        occ0 = self.occupancy()
        spans0 = self.span_totals()
        vm0, res0, dec0 = self.n_pushed, self.n_results, self.n_decided
        origin = self._next_stamp()[0]
        t0 = time.perf_counter()
        end = t0 + seconds
        due_wall, push_wall = [], []
        while True:
            stamp, is_cap = self._next_stamp()
            now = time.perf_counter()
            if paced:
                due = t0 + (stamp - origin)
                if due >= end:
                    break
                if due > now:
                    time.sleep(due - now)
                    now = time.perf_counter()
            elif now >= end:
                break
            d = self.step()
            took = time.perf_counter() - now
            if took > self.longest[0]:
                self.longest = (took, "power sweep" if d is None
                                else "deployment")
            if d is not None:
                due_wall.append(due if paced else now)
                push_wall.append(now)
        t_end = time.perf_counter() if not paced else end
        self.win = {
            "seconds": t_end - t0, "t0": t0, "origin": origin,
            "paced": paced, "rate_vm_per_s": self.rate,
            "attempted": int(self.n_pushed - vm0),
            "decided": int(self.n_decided - dec0),
            "batches": int(self.n_results - res0),
            "vm_range": (vm0, self.n_pushed), "due_wall": due_wall,
            "gen_lag_s": np.asarray(push_wall) - np.asarray(due_wall),
            "spans": _delta(spans0, self.span_totals())}
        self.win["log"] = {
            "occupancy_cores_start": occ0,
            "occupancy_cores_end": self.occupancy(),
            "rejected_share": round(float(
                (self.server[vm0:self.n_decided] < 0).mean()), 4)
            if self.n_decided > vm0 else None,
            "longest_push_s": round(self.longest[0], 4),
            "longest_push": repr(self.longest[1])}
        return self.win

    def drain(self, win: dict) -> None:
        """After the window: keep serving (open loop: at the stamps) until
        every arrival of the window has its decision, for at most a
        minute, then flush. Fills in the per-arrival latencies."""
        vm0, vm1 = win["vm_range"]
        deadline = time.perf_counter() + 60.0
        while win["paced"] and self.n_decided < vm1 \
                and time.perf_counter() < deadline:
            due = win["t0"] + (self._next_stamp()[0] - win["origin"])
            now = time.perf_counter()
            if due > now:
                time.sleep(due - now)
            self.step()
        tail = self.pipe.flush()
        self._snap(flushed=True)
        self._take([] if tail is None else [tail], time.perf_counter())
        import jax
        jax.block_until_ready(self.pipe.state)
        first = np.searchsorted(self.stream.start[:-1], vm0)
        sizes = np.diff(self.stream.start[first:first + len(win["due_wall"])
                                          + 1])
        vm_due = np.repeat(np.asarray(win["due_wall"]), sizes)
        win["failed"] = int((self.server[vm0:vm1] == -9).sum())
        win["latency_s"] = self.t_ret[vm0:vm1] - vm_due
        win["batch_wait_s"] = self._batch_waits(vm0, vm1, vm_due)

    def _batch_waits(self, vm0, vm1, vm_due) -> np.ndarray:
        """Per micro-batch of the window: from the due time of its first
        arrival to the return of the call that decided it."""
        bs = self.cfg["batch_size"]
        first = np.arange(-(-vm0 // bs) * bs, vm1, bs)
        first = first[first + bs <= vm1]
        return self.t_ret[first + bs - 1] - vm_due[first - vm0]

    def span_totals(self) -> dict:
        obs = self.pipe.obs
        if obs is None or obs.tracer is None:
            return {}
        return obs.tracer.totals()

    def release(self) -> None:
        """Read the final state to the host and drop the pipeline."""
        import jax
        st = jax.device_get(self.pipe.state)
        self.final = {k: np.asarray(getattr(st, k), np.float64) for k in
                      ("free_cores", "gamma_uf", "gamma_nuf", "res_peak",
                       "mem_nuf")}
        self.caps = [_plane_state(*jax.device_get(c)) for c in self.caps]
        self.pipe = None

    # -- the comparison ----------------------------------------------------
    def check(self, win: dict, control: str | None = None) -> dict:
        """Replay the pushed log through the reference. With `control`
        (a precision name) the reference computed in that precision is
        put in the program's place: its heads and decisions are what
        is compared."""
        return replay(self, win, control and R.Arith(control))


def replay(cell: ServeCell, win: dict, control=None) -> dict:
    """Teacher-forced replay: for each arrival of the window the
    reference decides from a ledger that then takes the program's own
    decision, so one disagreement is counted once and does not
    cascade. Compared: the gated heads, every decision, the final
    ledgers, and arrivals left undecided."""
    cfg = cell.cfg
    ref = R.Arith("float32")
    vm0, vm1 = win["vm_range"]
    v = cell.stream.vms
    sl = slice(vm0, vm1)
    cols = (v.subscription[sl], v.cores[sl], v.memory_gb[sl], v.vm_type[sl])
    q = R.query(cell.world.service, cell.world.sums.features(*cols, ref), ref)
    if control is None:
        got_wt, got_bucket = cell.wt[sl], cell.bucket[sl]
    else:
        qc = R.query(cell.world.service,
                     cell.world.sums.features(*cols, control), control)
        got_wt, got_bucket = qc["workload_type"], qc["p95_bucket"]
    head_mismatch = int(((q["workload_type"] != got_wt)
                         | (q["p95_bucket"] != got_bucket)).sum())
    blades, cps = cfg["blades_per_chassis"], cfg["cores_per_server"]
    led = R.Ledger(cell.n_servers, cps, blades)
    n_ch = cell.n_servers // blades
    p_dyn = (R.PEAK_W - R.IDLE_W) / R.CORES_PER_BLADE
    base = R.rho_cap(cfg["chassis_budget_w"], blades, R.IDLE_W, p_dyn, n_ch)
    caps = np.column_stack([base, np.full((n_ch, 2), np.inf, np.float32)])
    adapt = R.Adaptive(dict(ADAPTIVE, static_w=blades * R.IDLE_W,
                            p_dyn_per_core=p_dyn), n_ch) \
        if cfg["planes"]["adaptive"] else None
    emer = R.Emergency(cfg["chassis_budget_w"], blades, ref) \
        if cfg["planes"]["emergency"] else None
    emer_c = control and emer and R.Emergency(cfg["chassis_budget_w"],
                                              blades, control)
    # the same comparison with no room for rounding, read alongside
    emer0 = emer and R.Emergency(cfg["chassis_budget_w"], blades, ref,
                                 delta_w=0.0, delta_s=0.0)
    pre = R.Emergency.init(n_ch)
    cap_mismatch = cap_exact = cap_ties = cap_n = 0
    epoch = None
    server = cell.server
    p95_eff = R.bucket_to_p95(cell.bucket)
    is_uf = cell.wt == R.UF
    bs = cfg["batch_size"]
    mismatch = queued = served = 0
    # the batches whose every arrival is decided again: all batches of
    # the window, or a sample drawn from the seed when there are more
    b0, b1 = vm0 // bs, -(-vm1 // bs)
    picked = np.arange(b0, b1)
    if len(picked) > MAX_CHECKED_BATCHES:
        picked = np.random.default_rng([cell.seed, 7]).choice(
            picked, MAX_CHECKED_BATCHES, replace=False)
    picked = set(picked.tolist())

    def decide(i, ar):
        srv = R.choose(led, v.cores[i], bool(is_uf[i]), cfg["policy"], ar)
        if srv >= 0 and not R.admit(led, srv, v.cores[i], p95_eff[i],
                                    v.memory_gb[i], caps, ar):
            srv = R.FAIL_POWER
        return srv

    def serve(lo, hi):
        nonlocal mismatch
        if hi <= vm0 or lo >= vm1 or lo // bs not in picked:
            led.apply(server[lo:hi], v.cores[lo:hi], p95_eff[lo:hi],
                      is_uf[lo:hi], v.memory_gb[lo:hi])
            return
        for i in range(lo, hi):
            if vm0 <= i < vm1:
                got = server[i] if control is None else decide(i, control)
                mismatch += int(decide(i, ref) != got)
            if server[i] >= 0:
                led.apply_one(int(server[i]), float(v.cores[i]),
                              float(p95_eff[i]), bool(is_uf[i]),
                              float(v.memory_gb[i]))

    for ev in cell.log:
        if ev[0] == "A":
            queued += ev[2] - ev[1]
            while queued >= bs:
                serve(served, served + bs)
                served += bs
                queued -= bs
        elif ev[0] == "D":
            led.apply(ev[1], ev[2], ev[3], ev[4], ev[5], sign=-1.0)
        else:
            if adapt is not None:
                adapt.step(led.rho_levels(), ev[1], ev[2])
                caps[:, 0] = np.float32(base * adapt.ratio)
            if emer is not None:
                epoch = ev[3][0] if epoch is None else epoch
                post = cell.caps[ev[4]]
                bad, ties = compare_sweep(emer, emer_c, pre, post, led, ev,
                                          epoch)
                cap_mismatch += bad
                cap_ties += ties
                cap_n += len(ev[1])
                cap_exact += compare_sweep(emer0, emer_c, pre, post, led, ev,
                                           epoch)[0]
                pre = post
    serve(served, served + queued)          # the final flush
    mine = {"free_cores": led.free, "gamma_uf": led.g_uf,
            "gamma_nuf": led.g_nuf, "res_peak": led.res,
            "mem_nuf": led.mem_nuf}
    got = cell.final if control is None else \
        {k: control.r(a) for k, a in mine.items()}
    ledger_err = max(float(np.abs(got[k] - mine[k]).max()) for k in mine)
    out = {"head_mismatch": head_mismatch, "decision_mismatch": mismatch,
           "ledger_err": ledger_err, "undecided": win["failed"]}
    if emer is not None:
        out.update(cap_mismatch=cap_mismatch, cap_mismatch_exact=cap_exact,
                   cap_ties=cap_ties, cap_chassis_sweeps=cap_n)
    return out


def compare_sweep(emer, emer_c, pre, post, led, ev, epoch):
    """One power sweep from the program's state before it: the chassis
    whose p-states, RAPL or standing balloon (beyond 0.1 %) is none of
    the outcomes the reference reaches within rounding. With `emer_c`
    (the control) its result stands in for the program's. Returns the
    number of such chassis and the number with more than one outcome."""
    chassis, power, t = ev[1], ev[2], np.asarray(ev[3]) - epoch
    if len(set(np.asarray(chassis).tolist())) != len(chassis):
        raise ValueError("a power sweep samples each chassis once")
    args = (led.rho_levels(), led.mem_nuf, chassis, power, t)
    got = post if emer_c is None else emer_c.step(pre, *args)[0]
    out = emer.step(pre, *args, got=got)[1]
    idx = np.arange(len(pre["rapl"]))
    p_ok = out["pstate"][idx[:, None], np.arange(2), got["pstate"]].all(-1)
    r_ok = out["rapl"][idx, np.asarray(got["rapl"]).astype(np.int64)]
    cand = out["balloon"]
    tol = 1e-3 * np.maximum(1.0, np.abs(cand))
    with np.errstate(invalid="ignore"):
        b_ok = (np.abs(np.asarray(got["ballooned"])[:, None] - cand)
                <= tol).any(-1)
        two = np.isfinite(cand).all(-1) \
            & (np.abs(cand[:, 0] - cand[:, 1]) > tol[:, 0])
    many = (out["pstate"].sum(-1) > 1).any(-1) | out["rapl"].all(-1) | two
    return int((~(p_ok & r_ok & b_ok)).sum()), int(many.sum())


def _plane_state(em, bal) -> dict:
    """The emergency and balloon state as the reference holds it."""
    st = {"pstate": np.asarray(em.pstate, np.int64),
          "rapl": np.asarray(em.rapl),
          "capped_s": np.asarray(em.capped_s),
          "clear_s": np.asarray(em.clear_s),
          "last_t": np.asarray(em.last_t)}
    st["ballooned"] = np.zeros(len(st["rapl"]), np.float32) if bal is None \
        else np.asarray(bal.ballooned_gb)
    return st


def _delta(before: dict, after: dict) -> dict:
    """Span (count, seconds) totals accrued between two readings."""
    return {k: (n - before.get(k, (0, 0.0))[0], t - before.get(k, (0, 0.0))[1])
            for k, (n, t) in after.items()}


#: the controller settings `AdaptiveConfig()` states (serve/adaptive.py)
ADAPTIVE = {"window": 16, "min_history": 4, "spread_q_lo": 0.1,
            "spread_q_hi": 0.9, "spread_thresh": 0.25, "flip_thresh": 0.6,
            "hot_util": 0.85, "ratchet_quorum": 0.9, "backoff_quorum": 0.5,
            "step_up": 0.05, "step_down": 0.25, "ratio_min": 1.0,
            "ratio_max": 2.0}
Cell = ServeCell
