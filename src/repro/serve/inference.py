"""Batched two-stage inference (serve-pipeline stage 2).

One jitted call evaluates all four forests of a trained
`PredictionService` (criticality, P95 stage 1, low- and high-bucket
stage 2) on an arrival micro-batch and fuses the paper's confidence
gating: low-confidence queries fall back to the conservative
user-facing @ bucket-3 answer the production scheduler uses (§IV-B).

Kernel routing mirrors `kernels/forest/ops`: on TPU the packed
operands feed the Pallas oblivious-forest kernel; elsewhere the same
operands run through the identical dense math in plain jnp (the
kernel's `ref.py` formulation) — interpret-mode Pallas is for
correctness tests, not serving. Operands are packed once per model
(`pack_service`), which is what makes hot-swap cheap."""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.forest import ObliviousForest
from repro.core.predictor import CONFIDENCE_GATE, UF, PredictionService
from repro.core.rounding import div
from repro.kernels.forest.ops import (
    normalize_forest_output, pack_forest, pack_matrices, predict_packed)

_HIGHEST = jax.lax.Precision.HIGHEST


class PackedForest(NamedTuple):
    gather: jnp.ndarray      # (F, T*D) one-hot feature gather
    thr: jnp.ndarray         # (1, T*D)
    pack: jnp.ndarray        # (T*D, T) power-of-two bit-pack
    expand: jnp.ndarray      # (T, T*2**D) one-hot leaf expansion
    leaf: jnp.ndarray        # (T*2**D, K) flat leaf table


@dataclass(frozen=True)
class ForestMeta:
    n_trees: int
    depth: int
    kind: str


class PackedService(NamedTuple):
    """Device operands of the four forests (same shapes across daily
    retrains with fixed hyperparameters — the hot-swap invariant)."""
    criticality: PackedForest
    stage1: PackedForest
    low: PackedForest
    high: PackedForest


@dataclass(frozen=True)
class ServiceMeta:
    """Static (hashable) companion of a PackedService for jit."""
    criticality: ForestMeta
    stage1: ForestMeta
    low: ForestMeta
    high: ForestMeta
    confidence_gate: float = CONFIDENCE_GATE
    n_features: int = 0


def _pack_one(forest: ObliviousForest) -> tuple[PackedForest, ForestMeta]:
    *operands, t, d, kind = pack_forest(forest)
    return PackedForest(*operands), ForestMeta(t, d, kind)


def pack_service(svc: PredictionService) \
        -> tuple[PackedService, ServiceMeta]:
    """Pack all four of a service's forests into device operands +
    static metadata — done once per (re)trained model; this is what
    makes the pipeline's hot swap a buffer flip."""
    forests = (svc.criticality, svc.p95.stage1, svc.p95.low, svc.p95.high)
    packed, metas = zip(*(_pack_one(f) for f in forests))
    return (PackedService(*packed),
            ServiceMeta(*metas, confidence_gate=svc.confidence_gate,
                        n_features=svc.criticality.n_features))


def _proba_ref(x, pf: PackedForest, meta: ForestMeta):
    """The Pallas kernel's math in plain jnp (XLA path off-TPU)."""
    t, d = meta.n_trees, meta.depth
    levels = jnp.dot(x, pf.gather, precision=_HIGHEST,
                     preferred_element_type=jnp.float32)
    bits = (levels > pf.thr).astype(jnp.int32).reshape(-1, t, d)
    weights = (2 ** jnp.arange(d))[::-1]
    leaf_idx = (bits * weights[None, None]).sum(-1)           # (B, T)
    leaf = pf.leaf.reshape(t, 1 << d, -1)
    summed = leaf[jnp.arange(t)[None], leaf_idx].sum(1)       # (B, K)
    return _finish(summed, meta)


def _proba_pallas(x, pf: PackedForest, meta: ForestMeta, interpret,
                  block_b=None, block_t=None):
    kw = {} if block_b is None else {"block_b": block_b}
    return predict_packed(x, *pf, meta.n_trees, meta.depth, meta.kind,
                          interpret, block_t=block_t, **kw)


def _finish(summed, meta: ForestMeta):
    return normalize_forest_output(summed, meta.kind, meta.n_trees)


@lru_cache(maxsize=None)
def _measured_fallback() -> str:
    """Pick the off-TPU kernel by measurement, once per process: time
    the interpret-mode tiled Pallas kernel against the plain-jnp
    reference on a tiny synthetic forest and return the faster name.
    In practice XLA's fused dense math wins by orders of magnitude
    (interpret mode emulates the grid program-by-program), but the
    routing is measured rather than assumed — a backend where
    interpret mode compiles well would flip automatically, and
    `benchmarks/forest_kernel.py` tracks the same ratio."""
    import time

    t, d, f, k, b = 4, 3, 8, 2, 32
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(b, f)).astype(np.float32))
    gather = jnp.asarray(
        np.eye(f, dtype=np.float32)[:, rng.integers(0, f, t * d)])
    thr = jnp.asarray(rng.normal(size=(1, t * d)).astype(np.float32))
    leaf = jnp.asarray(
        rng.normal(size=(t * (1 << d), k)).astype(np.float32))
    pack, expand = (jnp.asarray(m) for m in pack_matrices(t, d))
    pf = PackedForest(gather, thr, pack, expand, leaf)
    meta = ForestMeta(t, d, "rf")

    def timed(fn):
        fn().block_until_ready()            # compile outside the clock
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            fn().block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    t_ref = timed(jax.jit(lambda: _proba_ref(x, pf, meta)).lower()
                  .compile())
    t_pal = timed(jax.jit(
        lambda: _proba_pallas(x, pf, meta, interpret=True,
                              block_b=b, block_t=2)).lower().compile())
    return "ref" if t_ref <= t_pal else "pallas_interpret"


def resolve_kernel(kernel: str = "auto") -> str:
    """Resolve 'auto' to the Pallas kernel on TPU and the *measured*
    faster of {jnp reference, interpret-mode Pallas} elsewhere
    (`_measured_fallback`); explicit names pass through."""
    if kernel == "auto":
        return "pallas" if jax.default_backend() == "tpu" \
            else _measured_fallback()
    return kernel


def _proba4_ref_stacked(x, packed: PackedService, meta: ForestMeta):
    """All four forests in one gather-matmul pass (they share shapes
    whenever `train_service` used one hyperparameter set — the common
    case). Returns a list of four (B, K) probability arrays."""
    t, d = meta.n_trees, meta.depth
    pfs = list(packed)
    gather = jnp.concatenate([pf.gather for pf in pfs], 1)  # (F, 4*T*D)
    thr = jnp.concatenate([pf.thr for pf in pfs], 1)
    leaf = jnp.stack([pf.leaf.reshape(t, 1 << d, -1) for pf in pfs])
    levels = jnp.dot(x, gather, precision=_HIGHEST,
                     preferred_element_type=jnp.float32)
    bits = (levels > thr).astype(jnp.int32).reshape(-1, 4 * t, d)
    weights = (2 ** jnp.arange(d))[::-1]
    leaf_idx = (bits * weights[None, None]).sum(-1) \
        .reshape(-1, 4, t)                                   # (B, 4, T)
    fi = jnp.arange(4)[None, :, None]
    ti = jnp.arange(t)[None, None, :]
    vals = leaf[fi, ti, leaf_idx]                            # (B, 4, T, K)
    return [_finish(vals[:, f].sum(1), meta) for f in range(4)]


@partial(jax.jit, static_argnames=("meta", "kernel"))
def served_query(packed: PackedService, meta: ServiceMeta,
                 x: jnp.ndarray, kernel: str = "ref") -> dict:
    """x: (B, F) features -> the `PredictionService.query` dict as
    device arrays, with the conservative fallback fused in. Extra key
    `conservative` marks arrivals that hit either fallback.

    Both the gated (``*_used``) and raw (``workload_type`` /
    ``p95_bucket``) heads plus their confidences are returned: the
    pipeline places on the gated values, and the prediction scorecard
    (`obs.quality`) fetches the raw heads alongside them in the same
    commit `device_get` — outputs only, so scoring can never perturb
    a decision."""
    assert x.shape[1] == meta.n_features, \
        f"feature width {x.shape[1]} != model's {meta.n_features}"
    x = x.astype(jnp.float32)
    metas = (meta.criticality, meta.stage1, meta.low, meta.high)
    if kernel == "ref" and len(set(metas)) == 1:
        pc, p1, plo, phi = _proba4_ref_stacked(x, packed,
                                               meta.criticality)
    else:
        if kernel == "pallas":
            proba = partial(_proba_pallas, interpret=False)
        elif kernel == "pallas_interpret":
            proba = partial(_proba_pallas, interpret=True)
        else:
            proba = _proba_ref
        pc = proba(x, packed.criticality, meta.criticality)
        p1 = proba(x, packed.stage1, meta.stage1)
        plo = proba(x, packed.low, meta.low)
        phi = proba(x, packed.high, meta.high)

    wt, wt_conf = pc.argmax(-1), pc.max(-1)
    s1 = p1.argmax(-1)
    bucket = jnp.where(s1 == 1, phi.argmax(-1) + 2, plo.argmax(-1))
    pb_conf = jnp.minimum(p1.max(-1),
                          jnp.where(s1 == 1, phi.max(-1), plo.max(-1)))
    gate = meta.confidence_gate
    wt_used = jnp.where(wt_conf >= gate, wt, UF)
    pb_used = jnp.where(pb_conf >= gate, bucket, 3)
    return {"workload_type": wt, "workload_conf": wt_conf,
            "p95_bucket": bucket, "p95_conf": pb_conf,
            "workload_type_used": wt_used, "p95_bucket_used": pb_used,
            "conservative": (wt_conf < gate) | (pb_conf < gate)}


def bucket_to_p95_jnp(bucket: jnp.ndarray) -> jnp.ndarray:
    """jnp twin of `core.predictor.bucket_to_p95` (bucket midpoint)."""
    return div(bucket.astype(jnp.float32) * 25.0 + 12.5, 100.0, jnp)
