"""Per-kernel validation: shape/dtype sweeps against the pure-jnp
oracles (interpret=True executes the Pallas kernel bodies on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.forest import train_gradient_boosting, train_random_forest
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.forest.forest import (forest_predict_pallas,
                                         resolve_block_t)
from repro.kernels.forest.ops import forest_predict, pack_forest
from repro.kernels.forest.ref import forest_predict_ref
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_ref
from repro.kernels.template.ops import criticality_scores
from repro.kernels.template.ref import criticality_scores_ref

RNG = np.random.default_rng(0)


def randn(*shape, dtype=np.float32):
    return jnp.asarray(RNG.normal(0, 1, shape).astype(dtype))


# --- template ------------------------------------------------------------

@pytest.mark.parametrize("batch,days", [(8, 5), (130, 5), (32, 10)])
def test_template_kernel_vs_oracle(batch, days):
    series = jnp.asarray(
        RNG.uniform(0, 100, (batch, days * 48)).astype(np.float32))
    out = criticality_scores(series, block_b=8)
    ref = criticality_scores_ref(series)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-3, atol=5e-4)


def test_template_kernel_classification_agreement():
    from repro.sim.telemetry import generate_population
    pop = generate_population(200, seed=9)
    s = jnp.asarray(pop.series)
    out = np.asarray(criticality_scores(s))
    ref = np.asarray(criticality_scores_ref(s))
    assert ((out[:, 0] < 0.72) == (ref[:, 0] < 0.72)).mean() == 1.0


# --- forest ----------------------------------------------------------------

@pytest.mark.parametrize("trainer,kind", [(train_random_forest, "rf"),
                                          (train_gradient_boosting, "gb")])
@pytest.mark.parametrize("n_classes", [2, 4])
def test_forest_kernel_vs_oracle(trainer, kind, n_classes):
    x = RNG.normal(0, 1, (300, 7)).astype(np.float32)
    y = RNG.integers(0, n_classes, 300)
    y[x[:, 0] > 0] = 0
    f = trainer(x, y, n_classes, n_trees=12, depth=4)
    p_np = f.predict_proba_np(x)
    p_ref = np.asarray(forest_predict_ref(
        jnp.asarray(x), jnp.asarray(f.feat_idx),
        jnp.asarray(f.thresholds), jnp.asarray(f.leaf_values), kind))
    p_pal = np.asarray(forest_predict(f, x))
    np.testing.assert_allclose(p_ref, p_np, atol=1e-5)
    np.testing.assert_allclose(p_pal, p_np, atol=1e-5)


@pytest.fixture(scope="module")
def packed_forest():
    # 128 trees of depth 4: 32-, 64- and 128-tree tiles all span a
    # multiple of 128 gather columns, so the sweep covers 1, 2 and 4
    # tree tiles
    x = RNG.normal(0, 1, (300, 7)).astype(np.float32)
    y = RNG.integers(0, 3, 300)
    y[x[:, 0] > 0.3] = 0
    f = train_random_forest(x, y, 3, n_trees=128, depth=4)
    return f, x, f.predict_proba_np(x), pack_forest(f)


@pytest.mark.parametrize("block_b", [32, 128])
@pytest.mark.parametrize("block_t,tile", [(1, 128), (32, 32), (40, 32),
                                          (64, 64), (100, 64),
                                          (128, 128)])
def test_forest_tiled_kernel_tile_shape_parity(packed_forest, block_b,
                                               block_t, tile):
    """The (batch, trees) grid tiling is a pure execution-schedule
    choice: every tile shape, with the tile corners of the bit-pack
    and expansion matrices, must reproduce the ref.py oracle
    bit-for-bit up to float accumulation order."""
    f, x, p_np, (gather, thr, pack, expand, leaf, t, d, kind) = \
        packed_forest
    assert resolve_block_t(t, d, block_t) == tile
    b = x.shape[0]
    pad = (-b) % block_b
    xp = jnp.asarray(np.vstack([x, np.zeros((pad, x.shape[1]),
                                            np.float32)]))
    summed = forest_predict_pallas(xp, gather, thr, pack, expand, leaf,
                                   t, d, block_b=block_b,
                                   block_t=block_t, interpret=True)[:b]
    p_ref = np.asarray(forest_predict_ref(
        jnp.asarray(x), jnp.asarray(f.feat_idx),
        jnp.asarray(f.thresholds), jnp.asarray(f.leaf_values), kind))
    np.testing.assert_allclose(np.asarray(summed) / t, p_ref, atol=1e-5)
    np.testing.assert_allclose(np.asarray(summed) / t, p_np, atol=1e-5)


def test_pack_matrices_rebuild_leaf_index(packed_forest):
    """bits @ P is the bit-packed leaf index and (leaf_idx @ E) == j the
    one-hot over each tree's leaves."""
    f, x, _, (gather, thr, pack, expand, leaf, t, d, kind) = \
        packed_forest
    bits = (x @ np.asarray(gather) > np.asarray(thr)).astype(np.float32)
    leaf_idx = bits @ np.asarray(pack)
    want = (bits.reshape(-1, t, d) * 2.0 ** np.arange(d)[::-1]).sum(-1)
    np.testing.assert_array_equal(leaf_idx, want)
    onehot = (leaf_idx @ np.asarray(expand)
              == np.arange(t << d) % (1 << d))
    assert (onehot.reshape(-1, t, 1 << d).sum(-1) == 1).all()
    np.testing.assert_array_equal(
        onehot.reshape(-1, t, 1 << d).argmax(-1), want)


def test_resolve_block_t_clamps_to_divisor():
    assert resolve_block_t(12, 4, None) == 12
    assert resolve_block_t(12, 4, 48) == 12
    assert resolve_block_t(128, 4, 100) == 64   # largest lane-aligned
    assert resolve_block_t(128, 4, 31) == 128   # none <= 31: whole
    assert resolve_block_t(48, 6, 24) == 48     # 24*6 not lane-aligned
    assert resolve_block_t(128, 6, 64) == 64    # 64*6 = 3 * 128
    assert resolve_block_t(7, 4, 3) == 7        # prime ensemble


# --- flash attention -------------------------------------------------------

@pytest.mark.parametrize("lq,lk,window", [
    (128, 128, None), (256, 256, 64), (64, 192, None), (100, 200, 50)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_vs_ref(lq, lk, window, dtype):
    q = randn(2, 4, lq, 32).astype(dtype)
    k = randn(2, 2, lk, 32).astype(dtype)
    v = randn(2, 2, lk, 32).astype(dtype)
    out = flash_attention(q, k, v, causal=True, window=window,
                          bq=64, bk=64)
    kr = jnp.repeat(k, 2, 1)
    vr = jnp.repeat(v, 2, 1)
    ref = attention_ref(q.astype(jnp.float32), kr.astype(jnp.float32),
                        vr.astype(jnp.float32), causal=True,
                        window=window)
    tol = 2e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_flash_non_causal():
    q, k, v = randn(1, 2, 64, 16), randn(1, 2, 96, 16), randn(1, 2, 96, 16)
    out = flash_attention(q, k, v, causal=False, bq=32, bk=32)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


# --- ssd -------------------------------------------------------------------

@pytest.mark.parametrize("l,chunk", [(64, 16), (96, 32), (100, 32),
                                     (128, 128)])
def test_ssd_vs_recurrence(l, chunk):
    B, H, P, N = 2, 3, 16, 8
    x = randn(B, l, H, P)
    dt = jnp.asarray(RNG.uniform(0.001, 0.2, (B, l, H)).astype(np.float32))
    a = jnp.asarray(-RNG.uniform(0.3, 2.0, H).astype(np.float32))
    bm, cm = randn(B, l, N), randn(B, l, N)
    d = randn(H)
    y = ssd(x, dt, a, bm, cm, d, chunk=chunk)
    yr, _ = ssd_ref(x, dt, a, bm, cm, d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-4)


def test_ssd_chunk_invariance():
    """Property: the chunked dual form is exact — results must not
    depend on the chunk size."""
    B, L, H, P, N = 1, 128, 2, 8, 4
    x = randn(B, L, H, P)
    dt = jnp.asarray(RNG.uniform(0.01, 0.1, (B, L, H)).astype(np.float32))
    a = jnp.asarray(-RNG.uniform(0.5, 1.0, H).astype(np.float32))
    bm, cm = randn(B, L, N), randn(B, L, N)
    d = randn(H)
    outs = [np.asarray(ssd(x, dt, a, bm, cm, d, chunk=c))
            for c in (16, 32, 64)]
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-4)
    np.testing.assert_allclose(outs[1], outs[2], atol=1e-4)


def test_ssd_state_decay_property():
    """With dt -> 0 the SSD is the identity-decay system: y ~ D*x."""
    B, L, H, P, N = 1, 32, 2, 8, 4
    x = randn(B, L, H, P)
    dt = jnp.full((B, L, H), 1e-8)
    a = jnp.asarray(np.full(H, -1.0, np.float32))
    bm, cm = randn(B, L, N), randn(B, L, N)
    d = jnp.asarray(np.full(H, 2.0, np.float32))
    y = np.asarray(ssd(x, dt, a, bm, cm, d, chunk=16))
    np.testing.assert_allclose(y, 2.0 * np.asarray(x), atol=1e-4)
