"""Jitted public wrapper: serve a trained ObliviousForest on TPU.

Precomputes the dense gather, bit-pack and one-hot expansion matrices
and the flat leaf table once per model (cheap; models retrain daily in
the paper) and pads the query batch.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.forest import ObliviousForest
from repro.core.rounding import div
from repro.kernels.forest.forest import (BLOCK_B, BLOCK_T,
                                         forest_predict_pallas)


def pack_forest(forest: ObliviousForest):
    """Build the kernel's static operands from a trained forest:
    ``(gather, thr, pack, expand, leaf_tab, n_trees, depth, kind)``.
    `gather` (F, T*D) is the one-hot feature gather, `pack` (T*D, T)
    the power-of-two bit-pack matrix, `expand` (T, T*L) the one-hot
    leaf expansion and `leaf_tab` (T*L, K) the flat leaf table."""
    t, d = forest.feat_idx.shape
    f = forest.n_features
    gather = np.zeros((f, t * d), np.float32)
    gather[forest.feat_idx.reshape(-1), np.arange(t * d)] = 1.0
    thr = forest.thresholds.reshape(1, t * d).astype(np.float32)
    pack, expand = pack_matrices(t, d)
    leaf_tab = forest.leaf_values.reshape(t * (1 << d),
                                          forest.n_out).astype(np.float32)
    return (jnp.asarray(gather), jnp.asarray(thr), jnp.asarray(pack),
            jnp.asarray(expand), jnp.asarray(leaf_tab), t, d, forest.kind)


def pack_matrices(n_trees: int, depth: int):
    """The bit-pack matrix P (T*D, T), ``P[t*D+l, t] = 2^(D-1-l)``, and
    the one-hot expansion E (T, T*L), ``E[t, t*L+j] = 1`` — the two
    block-diagonal matrices that keep the kernel 2-D."""
    td, tl = n_trees * depth, n_trees << depth
    pack = np.zeros((td, n_trees), np.float32)
    pack[np.arange(td), np.arange(td) // depth] = \
        2.0 ** ((depth - 1) - np.arange(td) % depth)
    expand = np.zeros((n_trees, tl), np.float32)
    expand[np.arange(tl) >> depth, np.arange(tl)] = 1.0
    return pack, expand


def normalize_forest_output(summed, kind: str, n_trees: int):
    """Summed leaf values -> class probabilities: RF mean / GB softmax.
    The one definition shared by the kernel wrapper and the serving
    path's ref/stacked formulations."""
    if kind == "rf":
        return div(summed, n_trees, jnp)
    m = summed - summed.max(-1, keepdims=True)
    e = jnp.exp(m)
    return e / e.sum(-1, keepdims=True)


def predict_packed(x, gather, thr, pack, expand, leaf_tab, n_trees,
                   depth, kind, interpret, block_b: int = BLOCK_B,
                   block_t: int | None = BLOCK_T):
    """Pad the batch to `block_b`, run the tiled kernel on packed
    operands, and normalize. Traceable — shared by `_predict` and the
    serving path (`repro.serve.inference`)."""
    b = x.shape[0]
    pad = (-b) % block_b
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)], 0)
    summed = forest_predict_pallas(x.astype(jnp.float32), gather, thr,
                                   pack, expand, leaf_tab, n_trees, depth,
                                   block_b=block_b, block_t=block_t,
                                   interpret=interpret)[:b]
    return normalize_forest_output(summed, kind, n_trees)


_predict = partial(jax.jit,
                   static_argnames=("n_trees", "depth", "kind",
                                    "interpret", "block_b",
                                    "block_t"))(predict_packed)


def forest_predict(forest: ObliviousForest, x, interpret: bool | None = None):
    """(B, F) features -> (B, K) probabilities via the Pallas kernel."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _predict(jnp.asarray(x), *pack_forest(forest), interpret)
