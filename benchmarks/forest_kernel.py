"""Tiled oblivious-forest kernel timings (DESIGN.md §13).

Times the 2-D `(batch, trees)` Pallas grid across tile shapes against
the plain-jnp reference formulation on the same packed operands, and
records the fallback ratio `repro.serve.inference.resolve_kernel`
acts on. Off TPU the kernel runs in interpret mode (the grid is
emulated program by program) — slower than XLA's fused dense math,
though a well-tiled grid stays within a small factor at batch scale,
which is exactly why the routing is measured rather than assumed. The
artifact commits (a) parity at every tile shape asserted under a
clock, (b) the measured interpret/ref ratio behind the serving path's
fallback, and (c) the tiled kernel's throughput at the committed best
tile shape behind the regression gate.

Writes BENCH_forest_kernel.json. ``--smoke`` runs one small forest
(CI); ``--regress`` re-measures the committed best tile shape against
the baseline (the plain-jnp reference is re-measured and printed but
not gated — its sub-millisecond wall is bimodal across fresh
interpreters on small CI boxes, and the serving pipeline it powers is
already gated end-to-end by ``benchmarks.serve_online``).
"""
from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, regress_gate
from repro.core.forest import train_random_forest
from repro.kernels.forest.forest import (forest_predict_pallas,
                                         resolve_block_t)
from repro.kernels.forest.ops import pack_forest
from repro.kernels.forest.ref import forest_predict_ref

OUT_PATH = "BENCH_forest_kernel.json"

N_TREES, DEPTH, N_CLASSES, N_FEATURES = 24, 4, 4, 16
#: large enough that the ref wall is work-dominated, not dispatch-
#: dominated — per-process dispatch overhead varies ~2x on small CI
#: boxes and would otherwise flap the regression gate
BATCH = 4096
#: (block_b, block_t) sweep. 24 trees of depth 4 span 96 gather
#: columns, less than one 128-lane tile, so every tile holds the whole
#: ensemble (`resolve_block_t`) and the sweep is over batch tiles
TILES = ((4096, None), (512, None), (128, None))
SMOKE_TILES = ((128, None), (64, None))


def _best_of(fn, repeat: int = 7):
    """(result, us_per_call) by best-of — interpret-mode walls are
    one-sided noisy (GC + per-program dispatch), so the min is the
    stable statistic, same as the serving drivers' regress probes."""
    import time

    out = fn()                          # warmup / trace
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return out, best * 1e6


def _operands(smoke: bool, seed: int = 0):
    t, b = (12, 128) if smoke else (N_TREES, BATCH)
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (600, N_FEATURES)).astype(np.float32)
    y = rng.integers(0, N_CLASSES, 600)
    y[x[:, 0] > 0.3] = 0
    f = train_random_forest(x, y, N_CLASSES, n_trees=t, depth=DEPTH,
                            seed=seed)
    xq = jnp.asarray(rng.normal(0, 1, (b, N_FEATURES)).astype(np.float32))
    return f, xq


def _ref_fn(f):
    fi = jnp.asarray(f.feat_idx)
    th = jnp.asarray(f.thresholds)
    lv = jnp.asarray(f.leaf_values)
    return jax.jit(lambda x: forest_predict_ref(x, fi, th, lv, f.kind))


def _tiled_fn(packed, n_trees, block_b, block_t):
    *operands, _t, d, _kind = packed
    return jax.jit(lambda x: forest_predict_pallas(
        x, *operands, n_trees, d, block_b=block_b,
        block_t=block_t, interpret=jax.default_backend() != "tpu"))


def _time_tiles(f, xq, tiles) -> list:
    """One row per tile shape; parity vs the reference is asserted
    under the same clock that times the kernel."""
    packed = pack_forest(f)
    t, d = packed[5], packed[6]
    b = xq.shape[0]
    p_ref = np.asarray(_ref_fn(f)(xq))
    rows = []
    for block_b, block_t in tiles:
        bb = min(block_b, b)
        pad = (-b) % bb
        xp = jnp.concatenate(
            [xq, jnp.zeros((pad, xq.shape[1]), xq.dtype)], 0) \
            if pad else xq
        fn = _tiled_fn(packed, t, bb, block_t)
        summed, us = _best_of(
            lambda fn=fn, xp=xp: np.asarray(
                jax.block_until_ready(fn(xp))))
        np.testing.assert_allclose(summed[:b] / t, p_ref, atol=1e-5)
        bt = resolve_block_t(t, d, block_t)
        row = {"block_b": bb, "block_t": bt,
               "grid": [xp.shape[0] // bb, t // bt],
               "us_per_call": us, "rows_per_s": b / (us * 1e-6)}
        rows.append(row)
        emit(f"forest_kernel/tiled/b{bb}xt{bt}", us,
             f"grid={row['grid']} rows_per_s={row['rows_per_s']:.0f}")
    return rows


def run(out_path: str = OUT_PATH, smoke: bool = False) -> dict:
    from repro.serve.inference import resolve_kernel
    f, xq = _operands(smoke)
    b = xq.shape[0]
    _, us_ref = _best_of(
        lambda fn=_ref_fn(f): np.asarray(jax.block_until_ready(fn(xq))))
    emit("forest_kernel/ref", us_ref,
         f"rows_per_s={b / (us_ref * 1e-6):.0f}")
    rows = _time_tiles(f, xq, SMOKE_TILES if smoke else TILES)
    best = min(rows, key=lambda r: r["us_per_call"])
    out = {"n_trees": f.n_trees, "depth": DEPTH, "batch": b,
           "backend": jax.default_backend(),
           "ref": {"us_per_call": us_ref,
                   "rows_per_s": b / (us_ref * 1e-6)},
           "tiled": rows,
           "best_tile": [best["block_b"], best["block_t"]],
           "interpret_over_ref": best["us_per_call"] / us_ref,
           "resolve_kernel_auto": resolve_kernel("auto")}
    emit("forest_kernel/fallback", 0.0,
         f"auto={out['resolve_kernel_auto']} "
         f"interpret_over_ref={out['interpret_over_ref']:.1f}x")
    if not smoke:
        with open(out_path, "w") as fh:
            json.dump(out, fh, indent=2)
    return out


def regress(baseline: dict) -> list:
    """Benchmark-regression gate (``benchmarks.run --regress``):
    re-measure the committed best tile shape and fail on a >30%
    rows/s drop vs BENCH_forest_kernel.json. The reference path is
    printed for context but not gated (see module docstring)."""
    f, xq = _operands(smoke=False)
    b = xq.shape[0]
    _, us_ref = _best_of(
        lambda fn=_ref_fn(f): np.asarray(jax.block_until_ready(fn(xq))))
    emit("forest_kernel/ref", us_ref,
         f"rows_per_s={b / (us_ref * 1e-6):.0f} (not gated)")
    bb, bt = baseline["best_tile"]
    failures = []
    want = next(r for r in baseline["tiled"]
                if [r["block_b"], r["block_t"]] == [bb, bt])
    rows = _time_tiles(f, xq, ((bb, bt),))
    failures += regress_gate(f"forest_kernel/tiled/b{bb}xt{bt}/rows_per_s",
                             rows[0]["rows_per_s"], want["rows_per_s"])
    return failures


if __name__ == "__main__":
    if "--regress" in sys.argv:
        with open(OUT_PATH) as fh:
            sys.exit(1 if regress(json.load(fh)) else 0)
    run(smoke="--smoke" in sys.argv)
