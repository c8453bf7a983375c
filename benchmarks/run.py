"""Benchmark harness: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run             # all
  PYTHONPATH=src python -m benchmarks.run table4 fig7 # subset
  PYTHONPATH=src python -m benchmarks.run --check     # artifacts only
  PYTHONPATH=src python -m benchmarks.run --regress   # CI perf gate

Each driver row pins the JSON artifact it writes (None = stdout only),
so callers and CI can locate outputs without running anything. A
driver that declares an artifact must actually produce it — asserted
after every run, and checkable without running via ``--check``.

``--regress`` is the benchmark-regression gate: every artifact driver
exposes a ``--regress`` probe that re-measures a quick representative
configuration and fails (exit 1) if its throughput drops more than
30% below the committed BENCH_*.json baseline
(`benchmarks.common.REGRESS_THRESHOLD`). Each probe runs in a fresh
interpreter — the probes are noise-sensitive on small CI boxes, and a
parent process full of jitted executables and training state taxes
them measurably.
"""
from __future__ import annotations

import os
import subprocess
import sys

#: (name, import path, JSON output path or None) — run order.
DRIVERS = (
    ("table2", "benchmarks.table2_criticality", None),
    ("fig3", "benchmarks.fig3_scatter", None),
    ("table3", "benchmarks.table3_models", None),
    ("fig4_fig5", "benchmarks.fig4_5_server_capping", None),
    ("fig6", "benchmarks.fig6_chassis", None),
    ("fig7", "benchmarks.fig7_scheduler", None),
    ("table4", "benchmarks.table4_oversubscription", None),
    ("fleet", "benchmarks.fleet_engine", "BENCH_fleet_engine.json"),
    ("serve", "benchmarks.serve_online", "BENCH_serve.json"),
    ("serve_sharded", "benchmarks.serve_sharded",
     "BENCH_serve_sharded.json"),
    ("serve_ingest", "benchmarks.serve_ingest",
     "BENCH_serve_ingest.json"),
    ("serve_emergency", "benchmarks.serve_emergency",
     "BENCH_serve_emergency.json"),
    ("serve_quality", "benchmarks.serve_quality",
     "BENCH_serve_quality.json"),
    ("serve_adaptive", "benchmarks.serve_adaptive",
     "BENCH_serve_adaptive.json"),
    ("serve_resources", "benchmarks.serve_resources",
     "BENCH_serve_resources.json"),
    ("forest_kernel", "benchmarks.forest_kernel",
     "BENCH_forest_kernel.json"),
    ("roofline", "benchmarks.roofline_report", None),
)


def check_artifacts(ran: set | None = None) -> list:
    """Assert every BENCH_*.json the driver table lists exists on disk
    (all of them, or just the drivers in `ran`). Returns the paths."""
    missing = [out for name, _, out in DRIVERS
               if out and (ran is None or name in ran)
               and not os.path.exists(out)]
    assert not missing, f"driver table lists missing artifacts: {missing}"
    return [out for _, _, out in DRIVERS if out]


def regress() -> int:
    """Run every artifact driver's ``--regress`` probe against its
    committed baseline (see module docstring), one fresh interpreter
    each. Returns the number of failed gates."""
    from benchmarks.common import subproc_env
    check_artifacts()
    failed = []
    for name, module, out in DRIVERS:
        if not out:
            continue
        rc = subprocess.run(
            [sys.executable, "-m", module, "--regress"],
            env=subproc_env()).returncode
        print(f"regress,{name},{'ok' if rc == 0 else 'FAIL'}",
              flush=True)
        if rc:
            failed.append(name)
    for name in failed:
        print(f"REGRESS FAIL: {name}", file=sys.stderr)
    return len(failed)


def main() -> None:
    args = set(sys.argv[1:])
    if "--check" in args:
        for p in check_artifacts():
            print(f"artifact,{p},ok")
        return
    if "--regress" in args:
        sys.exit(1 if regress() else 0)
    want = args
    names = {name for name, _, _ in DRIVERS}

    def on(name):
        # exact driver names select only themselves ('serve' must not
        # drag in 'serve_sharded'); non-name tokens keep substring
        # matching ('fig4' -> fig4_fig5)
        if not want:
            return True
        return name in want or any(w in name and w not in names
                                   for w in want)

    print("name,us_per_call,derived")
    ran = set()
    for name, module, out in DRIVERS:
        if on(name):
            run = __import__(module, fromlist=["run"]).run
            run(out_path=out) if out else run()
            ran.add(name)
    check_artifacts(ran)


if __name__ == '__main__':
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
