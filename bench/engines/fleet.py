"""Fleet engine: the Table IV campus through `sim.fleet.run_fleet` with
the jitted backend, episode after episode, each with fresh per-chassis
seeds drawn from the run's seed; checked chassis by chassis against
`bench.reference.fleet_ref`."""
from __future__ import annotations

import time

import numpy as np

from bench.reference import fleet_ref
from bench.traffic import generator as gen


class FleetCell:
    """One fleet cell: the campus layout and budgets, the episode loop
    and the check."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, ann):
        from repro.sim.fleet import ServerSpec, VMSpec, build_layout
        self.cfg, self.traffic, self.ann, self.seed = cfg, traffic, ann, seed
        self.servers = [cfg["server_vms"]] * cfg["servers_per_chassis"]
        specs = [ServerSpec(vms=[VMSpec(v["cores"], v["uf"], v["load"])
                                 for v in vms], n_cores=cfg["cores_per_server"])
                 for vms in self.servers]
        self.specs = specs
        self.layout = build_layout(specs)
        rng = np.random.default_rng([seed, 4])
        self.budgets = rng.uniform(*cfg["budget_w"], cfg["n_chassis"])
        self.steps = traffic["steps"]
        self.episodes = []             # (episode index, power (B, T))
        self.k = 0

    def episode_seeds(self, k: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 5, k])
        return rng.integers(0, 2 ** 31 - 1, self.cfg["n_chassis"])

    def episode(self, keep: bool):
        from repro.sim.fleet import run_fleet
        seeds = self.episode_seeds(self.k)
        with self.ann("bench:run_fleet"):
            res = run_fleet(self.specs, self.budgets, self.cfg["mode"],
                            self.steps * self.cfg["poll_s"], seeds,
                            backend="jax", layout=self.layout)
        if keep:
            self.episodes.append((self.k, res.power_w))
        self.k += 1

    def warm_up(self) -> dict:
        for _ in range(self.traffic["warmup_episodes"]):
            self.episode(keep=False)
        return {"chassis": self.cfg["n_chassis"], "steps": self.steps}

    def window(self, seconds: float) -> dict:
        """Episodes back to back for `seconds`."""
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() < t0 + seconds:
            self.episode(keep=True)
            n += 1
        dt = time.perf_counter() - t0
        return {"seconds": dt, "episodes": n, "attempted": n,
                "failed": 0,
                "chassis_steps": n * self.cfg["n_chassis"] * self.steps}

    def drain(self, win: dict) -> None:
        """Every episode of the window returned its host arrays."""

    def release(self) -> None:
        """The episodes' outputs are host arrays already."""

    def check(self, win: dict, control: str | None = None) -> dict:
        """Max |program - reference| chassis draw (W) over every step of
        a seeded sample of (episode, chassis) pairs of the window. With
        `control` (a precision name) the reference computed in that
        precision is put in the program's place."""
        rng = np.random.default_rng([self.seed, 6])
        ch = fleet_ref.Chassis(self.servers)
        r = fleet_ref.rounding("float32")
        rc = control and fleet_ref.rounding(control)
        n = self.cfg["check_chassis"]
        picks = [(int(rng.integers(len(self.episodes))),
                  int(rng.integers(self.cfg["n_chassis"]))) for _ in range(n)]
        err = 0.0
        for e, c in picks:
            k, power = self.episodes[e]
            seed_c = int(self.episode_seeds(k)[c])
            traces = gen.uf_load_traces(seed_c, self.steps, ch.loads)
            ref = fleet_ref.simulate(ch, float(np.float32(self.budgets[c])),
                                     traces, r)
            got = power[c] if rc is None else \
                fleet_ref.simulate(ch, float(np.float32(self.budgets[c])),
                                   traces, rc)
            err = max(err, float(np.abs(ref.astype(np.float64)
                                        - got.astype(np.float64)).max()))
        return {"power_err_w": err, "undecided": win["failed"]}


Cell = FleetCell
