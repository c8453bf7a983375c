"""Run one cell of `BENCHMARK.json` once and print its result line.

Everything is found by name: the cell's configuration in
``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json``, the engine the configuration names
in ``bench/engines/<engine>.py`` and each metric's reader in
``bench/metrics/<metric>.py``. A new configuration, mix or metric is a
new file.

A run: set-up (the world and the pipeline from the seed, then the
warm-up traffic, compiles included), the measured window of
``--seconds``, then the comparison with the plain reference, which
decides ``correct``. ``--trace 1`` measures a window of
`TRACE_SECONDS` under the profiler and reports the per-layer metrics
instead of the end-to-end ones.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

from bench.trace_reduce import WINDOW_SPAN

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, workload: str, trace: bool) -> list:
    """The cell's end-to-end metrics (trace off) or per-layer ones."""
    pool = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in pool if workload in m.get("workloads", [workload])]


def reader_of(metric: str) -> Path:
    """The reader of a per-layer or end-to-end metric:
    ``bench/metrics/<metric>.py``, else the reader named by the part
    before its first dot (``device_idle.serve`` reads with
    ``device_idle.py``)."""
    path = BENCH / "metrics" / f"{metric}.py"
    return path if path.exists() else \
        BENCH / "metrics" / f"{metric.split('.')[0]}.py"


class CompileClock:
    """Counts and sums the seconds JAX spends compiling."""

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event.startswith("/jax/core/compile/backend_compile"):
            self.seconds += duration
            self.count += 1


#: the window of a ``--trace 1`` run, whole under the profiler: a few
#: seconds hold hundreds of batches (a 30 s trace is millions of
#: events, and stopping the profiler takes longer than the window)
TRACE_SECONDS = 5.0


def device_info(n_chips: int) -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs[:n_chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def log(**fields) -> None:
    print("[bench] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          file=sys.stderr, flush=True)


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, overrides: dict | None = None,
        control: str | None = None, traffic_overrides: dict | None = None,
        keep: dict | None = None) -> dict:
    """One run; returns the result line's object. `overrides` patches
    the configuration (the CPU rehearsals shrink it with this).
    `control` also reads the control's numbers (the reference in that
    precision put in the program's place) into ``control_checks``;
    `traffic_overrides` patches the traffic mix (the rate sweep); `keep`
    receives the window's raw record under "win"."""
    t_start = time.perf_counter()
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = find(bench["workloads"], workload, "workload")
    conf = find(bench["configs"], cell["config"], "config")
    cfg = load_json(ROOT / conf["file"])
    cfg.update(overrides or {})
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    traffic.update(traffic_overrides or {})
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < cell["chips"]:
        raise SystemExit(f"{workload} needs {cell['chips']} chips, JAX "
                         f"found {len(devs)}")
    peaks = load_json(BENCH / "peaks.json")["chips"]
    if trace and require_tpu and devs[0].device_kind not in peaks:
        raise SystemExit(f"no peaks for device kind "
                         f"{devs[0].device_kind!r} in bench/peaks.json")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock()

    def ann(name):
        return jax.profiler.TraceAnnotation(name) if trace \
            else contextlib.nullcontext()

    engine = importlib.import_module(f"bench.engines.{cfg['engine']}")
    c = engine.Cell(cfg, traffic, seed, ann)
    warm = c.warm_up()
    compiles0 = (clock.count, clock.seconds)
    setup_s = time.perf_counter() - t_start
    log(workload=workload, seed=seed, setup_s=round(setup_s, 3),
        compile_s=round(compiles0[1], 3), compiles=compiles0[0], **warm)
    red = None
    with tempfile.TemporaryDirectory(dir=ROOT / ".bench_out") \
            if trace else contextlib.nullcontext() as tdir:
        if trace:
            seconds = min(seconds, TRACE_SECONDS)
            jax.profiler.start_trace(tdir)
        # a program compiled inside the window is named on stderr
        jax.config.update("jax_log_compiles", True)
        try:
            with ann(WINDOW_SPAN):
                win = c.window(seconds)
        finally:
            jax.config.update("jax_log_compiles", False)
            if trace:
                jax.profiler.stop_trace()
        in_window = clock.count - compiles0[0]
        if trace:
            from bench import trace_reduce
            path = next(Path(tdir).rglob("*.xplane.pb"))
            events = trace_reduce.load(str(path))
            red = trace_reduce.reduce(events)
            if keep is not None:
                keep["trace_events"] = events
            red.update(batches=win.get("batches"),
                       episodes=win.get("episodes"))
    c.drain(win)
    dev = device_info(cell["chips"])
    log(window_s=round(win["seconds"], 3), attempted=win["attempted"],
        failed=win["failed"], compiles_in_window=in_window,
        memory_peak_bytes=dev["memory_peak_bytes"], **win.get("log", {}))
    c.release()
    t_check = time.perf_counter()
    checks = c.check(win)
    limits = cfg["limits"]
    correct = all(checks[k] <= limits[k] for k in limits)
    log(check_s=round(time.perf_counter() - t_check, 3),
        **{k: v for k, v in checks.items() if k not in limits})
    ctx = SimpleNamespace(setup_s=setup_s, win=win, trace=red, cfg=cfg,
                          traffic=traffic, cell=cell,
                          peaks=peaks.get(dev["kind"]), engine=c)
    metrics = {}
    for m in metrics_of(bench, workload, trace):
        val = load_module(reader_of(m["name"])).read(ctx)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    out = {"correct": correct, "attempted": win["attempted"],
           "failed": win["failed"], "metrics": metrics, "device": dev}
    if trace:
        from bench import trace_reduce
        out["device"]["busy_s"] = red.get("busy_s", 0.0)
        out["device"]["window_s"] = red.get("window_s", win["seconds"])
        out["breakdown"] = trace_reduce.breakdown(red)
    if keep is not None:
        keep.update(win=win, checks=checks)
    if control:
        out["control_checks"] = c.check(win, control=control)
    out["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                     for k in limits}
    for k in limits:
        print(f"check {k} {checks[k]} limit {limits[k]}", file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    (ROOT / ".bench_out").mkdir(exist_ok=True)
    out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
