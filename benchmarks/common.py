"""Shared helpers for the per-table benchmarks."""
from __future__ import annotations

import os
import time


def timed(fn, *args, repeat: int = 3, **kw):
    """Returns (result, us_per_call)."""
    fn(*args, **kw)                    # warmup / trace
    t0 = time.perf_counter()
    out = None
    for _ in range(repeat):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeat
    return out, dt * 1e6


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def subproc_env() -> dict:
    """Environment for a benchmark subprocess: PYTHONPATH includes
    `src` (the drivers import `repro.*` from the source tree)."""
    return dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in ("src", os.environ.get("PYTHONPATH")) if p))


def require_devices(n: int, name: str) -> None:
    """Fail unless this process sees at least `n` JAX devices. A driver
    runs in the process that calls it and never re-executes itself: on
    a CPU host, the caller forces devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before JAX
    starts."""
    import jax
    have = len(jax.devices())
    if have < n:
        raise RuntimeError(
            f"{name} needs {n} devices and this process has {have}; on "
            f"a CPU host set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}")


#: `benchmarks.run --regress` fails a driver whose re-measured
#: throughput drops below this fraction of its committed baseline.
REGRESS_THRESHOLD = 0.7


def regress_gate(name: str, measured: float, baseline: float,
                 threshold: float = REGRESS_THRESHOLD) -> list:
    """One benchmark-regression check: `measured` (higher is better)
    must reach `threshold` x the committed `baseline`. Prints the
    comparison; returns [] on pass or a one-line failure message."""
    ok = measured >= threshold * baseline
    print(f"regress,{name},measured={measured:.1f},"
          f"baseline={baseline:.1f},floor={threshold * baseline:.1f},"
          f"{'ok' if ok else 'FAIL'}", flush=True)
    if ok:
        return []
    return [f"{name}: measured {measured:.1f} < "
            f"{threshold:.0%} of baseline {baseline:.1f}"]
