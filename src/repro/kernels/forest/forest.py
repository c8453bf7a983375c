"""Pallas TPU kernel: oblivious-forest ensemble inference.

TPU adaptation (DESIGN.md §3): classic tree traversal is pointer-chasing;
oblivious trees make the whole ensemble dense algebra that maps onto the
MXU as four 2-D matmuls and two compares:

  1. feature gather  -> one-hot matmul:  (B, F) @ (F, T*D)  = levels
  2. compare         -> bits = levels > thresholds          (VPU)
  3. leaf index      -> bit-pack matmul: (B, T*D) @ (T*D, T) with the
                        power-of-two pack matrix P, P[t*D+l, t] =
                        2^(D-1-l)
  4. leaf one-hot    -> expansion matmul: (B, T) @ (T, T*L) with E,
                        E[t, t*L+j] = 1, then == j (the leaf position
                        within its tree, from an integer iota)
  5. leaf lookup     -> (B, T*L) @ (T*L, K) = summed leaf values

Everything stays 2-D: the chip's vector layout has no cheap
(B, T*D) <-> (B, T, D) reshape, so the per-tree reductions and
broadcasts of the bit-pack and the one-hot are the P and E matmuls.
Both are block-diagonal with identical blocks, so every tree tile uses
the same (BLOCK_T*D, BLOCK_T) and (BLOCK_T, BLOCK_T*L) corner of them.
Steps 3 and 4 are exact at any matmul precision (0/1 times powers of
two, sums < 2^D); steps 1 and 5 carry arbitrary f32 features and leaf
values, so they run at ``Precision.HIGHEST`` — the TPU's default f32
dot rounds its operands to bf16, which would move a feature across a
threshold and pick a wrong leaf.

Tiling (DESIGN.md §13): the kernel runs on a 2-D ``(batch, trees)``
grid. Each program instance evaluates one (BLOCK_B, BLOCK_T) tile and
accumulates its partial (BLOCK_B, K) sum into the output block. The
tree axis is the innermost grid dimension, so the output block for a
batch tile is revisited on consecutive iterations: ``@pl.when(j == 0)``
zero-initializes it, every tree tile adds its partial sum. A tree tile
narrower than the ensemble must span a multiple of 128 gather columns
(``BLOCK_T*D``, the lane width), which `resolve_block_t` enforces.

The ops.py wrapper precomputes the (F, T*D) one-hot gather matrix, the
P and E matrices and the (T*L, K) flattened leaf table from a trained
`ObliviousForest`, so the kernel itself is shape-static. All tile
shapes are parity-tested against ref.py (tests/test_kernels.py),
compiled for a described v5e (tests/test_tpu_compile.py) and measured
by benchmarks/forest_kernel.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_B = 128
#: Default tree-tile width (trees per program instance). None = all
#: trees in one tile (the pre-tiling layout, still optimal for the
#: small four-forest serving ensembles).
BLOCK_T = None
#: A tree tile narrower than the ensemble spans a multiple of this many
#: gather columns (the TPU lane width).
LANES = 128

_HIGHEST = jax.lax.Precision.HIGHEST


def _forest_kernel_tiled(x_ref, gather_ref, thr_ref, pack_ref,
                         expand_ref, leaf_ref, out_ref, *, depth: int):
    """One (batch-tile, tree-tile) program instance: partial leaf sums
    for one tile of trees, accumulated into the batch tile's output."""
    x = x_ref[...]                                # (B, F)
    gather = gather_ref[...]                      # (F, Tb*D)
    thr = thr_ref[...]                            # (1, Tb*D)
    pack = pack_ref[...]                          # (Tb*D, Tb)
    expand = expand_ref[...]                      # (Tb, Tb*L)
    leaf_tab = leaf_ref[...]                      # (Tb*L, K)

    # stage 1: feature gather + level compare + leaf-index bit-pack
    levels = jnp.dot(x, gather, precision=_HIGHEST,
                     preferred_element_type=jnp.float32)     # (B, Tb*D)
    bits = (levels > thr).astype(jnp.float32)
    leaf_idx = jnp.dot(bits, pack,
                       preferred_element_type=jnp.float32)   # (B, Tb)

    # stage 2: one-hot leaf lookup matmul for this tree slice
    spread = jnp.dot(leaf_idx, expand,
                     preferred_element_type=jnp.float32)     # (B, Tb*L)
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, expand.shape[1]), 1)
    leaf_pos = jnp.bitwise_and(pos, (1 << depth) - 1).astype(jnp.float32)
    onehot = (spread == leaf_pos).astype(jnp.float32)
    partial = jnp.dot(onehot, leaf_tab, precision=_HIGHEST,
                      preferred_element_type=jnp.float32)    # (B, K)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


def resolve_block_t(n_trees: int, depth: int, block_t: int | None) -> int:
    """Clamp a requested tree-tile width to one the chip accepts: the
    largest divisor of `n_trees` that is <= the request and spans a
    multiple of `LANES` gather columns (``block_t * depth``). Where no
    such divisor exists, the tile is the whole ensemble."""
    if block_t is None or block_t >= n_trees:
        return n_trees
    for bt in range(max(int(block_t), 1), 0, -1):
        if n_trees % bt == 0 and (bt * depth) % LANES == 0:
            return bt
    return n_trees


def forest_predict_pallas(x: jnp.ndarray, gather: jnp.ndarray,
                          thresholds_flat: jnp.ndarray,
                          pack: jnp.ndarray, expand: jnp.ndarray,
                          leaf_table: jnp.ndarray, n_trees: int,
                          depth: int, block_b: int = BLOCK_B,
                          block_t: int | None = BLOCK_T,
                          interpret: bool = False) -> jnp.ndarray:
    """Summed leaf values over trees: (B, K). Caller scales (RF mean) or
    softmaxes (GB). `pack` (T*D, T) and `expand` (T, T*L) are the
    ensemble's bit-pack and one-hot expansion matrices (`pack_forest`);
    only their leading tile corner is used. `block_b`/`block_t` pick
    the (batch, trees) tile; `block_t=None` puts the whole ensemble in
    one tile."""
    b, f = x.shape
    k = leaf_table.shape[1]
    assert b % block_b == 0
    block_t = resolve_block_t(n_trees, depth, block_t)
    n_leaves = 1 << depth
    td, tl = block_t * depth, block_t * n_leaves
    kernel = functools.partial(_forest_kernel_tiled, depth=depth)
    return pl.pallas_call(
        kernel,
        grid=(b // block_b, n_trees // block_t),
        in_specs=[
            pl.BlockSpec((block_b, f), lambda i, j: (i, 0)),
            pl.BlockSpec((f, td), lambda i, j: (0, j)),
            pl.BlockSpec((1, td), lambda i, j: (0, j)),
            pl.BlockSpec((td, block_t), lambda i, j: (0, 0)),
            pl.BlockSpec((block_t, tl), lambda i, j: (0, 0)),
            pl.BlockSpec((tl, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, k), jnp.float32),
        interpret=interpret,
    )(x, gather, thresholds_flat, pack[:td, :block_t],
      expand[:block_t, :tl], leaf_table)
