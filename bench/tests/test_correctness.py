"""`correct` at a size a test run holds, on the CPU: a sound run
passes, and the control (the reference in bfloat16 in the program's
place) and each fault a cell can have, planted in the timed path, make
it false. The harness's look for a chip is skipped; everything else is
a whole run."""
from dataclasses import replace

import numpy as np
import pytest

from bench import harness
from bench.rehearse import TINY

SEED = 3_000_000_019


def run(workload, **kw):
    cfg = TINY["fig7_cluster" if workload.startswith("fig7")
               else "table4_campus"]
    return harness.run(workload, SEED, 1.0, False, require_tpu=False,
                       overrides=cfg, **kw)


def failing(out):
    return {k for k, v in out["checks"].items() if v["value"] > v["limit"]}


@pytest.mark.parametrize("workload", ["fig7.saturate", "fig7.steady",
                                      "campus.fleet"])
def test_sound_run_is_correct_and_control_is_not(workload):
    out = run(workload, control="bf16")
    assert out["correct"], out["checks"]
    limits = {k: v["limit"] for k, v in out["checks"].items()}
    assert any(out["control_checks"][k] > limits[k] for k in limits), \
        out["control_checks"]


def _serve_fault(monkeypatch, kind):
    from repro.serve import pipeline, placement
    if kind == "cap_state_unchanged":
        orig_fn = pipeline._balloon_cap_step_fn

        def factory(ecfg, bcfg):
            fn = orig_fn(ecfg, bcfg)

            def step(gn, gu, cs, mem, emer, bst, pw, mask, ts):
                return (emer,) + tuple(fn(gn, gu, cs, mem, emer, bst, pw,
                                          mask, ts)[1:])
            return step
        monkeypatch.setattr(pipeline, "_balloon_cap_step_fn", factory)
        return
    if kind == "state_unchanged":
        orig = placement.place_batch

        def place(state, *a, **k):
            return state, orig(state, *a, **k)[1]
        monkeypatch.setattr(placement, "place_batch", place)
        return
    orig = pipeline.ServePipeline._serve_padded

    def serve(self, batch):
        if kind == "half_batch":
            # the second half is never placed: it comes back rejected
            from repro.serve.ingest import slice_soa
            half = len(batch) // 2
            res = orig(self, slice_soa(batch, 0, half))
            return replace(res, **{
                f: np.concatenate([getattr(res, f), np.full(
                    len(batch) - half, fill, getattr(res, f).dtype)])
                for f, fill in (("server", -1), ("workload_type", 1),
                                ("p95_bucket", 3), ("p95_eff", 0.875),
                                ("conservative", True))})
        # the first placement of the batch comes back as a rejection
        res = orig(self, batch)
        srv = res.server.copy()
        placed = np.nonzero(srv >= 0)[0]
        if len(placed):
            srv[placed[0]] = -2
        return replace(res, server=srv)
    monkeypatch.setattr(pipeline.ServePipeline, "_serve_padded", serve)


@pytest.mark.parametrize("kind", ["state_unchanged", "cap_state_unchanged",
                                  "half_batch", "answer_altered"])
def test_serve_fault_is_caught(monkeypatch, kind):
    _serve_fault(monkeypatch, kind)
    out = run("fig7.saturate")
    assert not out["correct"] and failing(out), out["checks"]


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered"])
def test_fleet_fault_is_caught(monkeypatch, kind):
    from repro.core import fleet_dynamics
    from repro.sim import fleet
    monkeypatch.setattr(fleet, "_ENGINE_CACHE", {})
    if kind == "state_unchanged":
        orig = fleet_dynamics.fleet_step

        def step(cp, rp, st, util, xp):
            return st, orig(cp, rp, st, util, xp)[1]
        monkeypatch.setattr(fleet, "fleet_step", step)
    else:
        orig = fleet.run_fleet

        def run_fleet(*a, **k):
            res = orig(*a, **k)
            p = np.array(res.power_w)
            if kind == "half_batch":
                p[len(p) // 2:] = 0.0
            else:
                p[:, 0] += 1.0
            res.power_w = p
            return res
        monkeypatch.setattr(fleet, "run_fleet", run_fleet)
    out = run("campus.fleet")
    assert not out["correct"] and failing(out), out["checks"]
