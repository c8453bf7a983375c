"""The world a serve cell runs in, made from the seed: the labelled
history, its per-subscription sums, and the four forests of the
prediction service.

The forests have the Table III shapes (48 trees of depth 6 over the 18
arrival features) and are fitted here, not by the program: every level
of a tree tests one feature at a threshold halfway between two values
the bootstrap sample holds, and each leaf keeps the Laplace-smoothed
label frequencies of the rows that reach it. Thresholds never equal a
feature value, so a feature one float32 ulp off cannot change a leaf.
The benchmark hands the same arrays to the program and to the
reference.
"""
from __future__ import annotations

import numpy as np

from bench.reference.serve_ref import SubscriptionSums, p95_bucket
from bench.traffic import generator as gen

#: the four forests of the two-stage service, in the reference's names
FORESTS = ("criticality", "stage1", "low", "high")


#: random splits tried per tree level; the one that separates the labels
#: best is kept
N_CANDIDATES = 4


def _random_split(rng, xb):
    """A random feature with at least two distinct values, and a
    threshold halfway between two neighbouring values in the central
    90 % of them."""
    while True:
        f = int(rng.integers(0, xb.shape[1]))
        u = np.unique(xb[:, f])
        if len(u) >= 2:
            break
    lo = int(0.05 * (len(u) - 1))
    hi = max(int(np.ceil(0.95 * (len(u) - 1))), lo + 1)
    j = int(rng.integers(lo, hi))
    return f, np.float32((float(u[j]) + float(u[j + 1])) / 2)


def fit_forest(rng, x, y, n_classes, n_trees, depth) -> dict:
    """Randomized oblivious forest: per level the best of
    `N_CANDIDATES` random splits by the squared label sums of the
    leaves (the variance-reduction score)."""
    n, n_feat = x.shape
    onehot = np.eye(n_classes)[y]
    prior = onehot.mean(0)
    fi = np.zeros((n_trees, depth), np.int32)
    th = np.zeros((n_trees, depth), np.float32)
    lv = np.zeros((n_trees, 1 << depth, n_classes), np.float32)
    for t in range(n_trees):
        idx = rng.integers(0, n, n)
        xb = x[idx]
        leaf = np.zeros(n, np.int64)
        for d in range(depth):
            best = None
            for _ in range(N_CANDIDATES):
                f, thr = _random_split(rng, xb)
                cand = leaf * 2 + (xb[:, f] > thr)
                cnt = np.bincount(cand, minlength=2 << d) + 1e-9
                score = sum(float((np.bincount(cand, onehot[idx, c],
                                               2 << d) ** 2 / cnt).sum())
                            for c in range(n_classes))
                if best is None or score > best[0]:
                    best = (score, f, thr, cand)
            _, fi[t, d], th[t, d], leaf = best
        cnt = np.bincount(leaf, minlength=1 << depth).astype(np.float64)
        sums = np.stack([np.bincount(leaf, weights=onehot[idx, c],
                                     minlength=1 << depth)
                         for c in range(n_classes)], 1)
        lv[t] = (sums + 2.0 * prior[None]) / (cnt[:, None] + 2.0)
    return {"feat_idx": fi, "thresholds": th, "leaf_values": lv}


class World:
    """History, subscription sums and forests of one seed."""

    def __init__(self, seed: int, n_history: int, n_trees: int, depth: int,
                 table_slack: int = 1024):
        self.rng = np.random.default_rng(seed)
        self.subs, hist = gen.history(self.rng, n_history)
        self.history = hist
        self.capacity = len(self.subs.uf_propensity) + table_slack
        self.sums = SubscriptionSums(
            self.capacity, hist.subscription, hist.user_facing,
            hist.lifetime_h, hist.p95_util, hist.avg_util)
        x = self.sums.features(hist.subscription, hist.cores,
                               hist.memory_gb, hist.vm_type)
        bucket = p95_bucket(hist.p95_util)
        over = bucket >= 2
        fit = lambda xs, ys: fit_forest(self.rng, xs, ys, 2, n_trees, depth)
        self.service = {
            "criticality": fit(x, hist.user_facing.astype(np.int64)),
            "stage1": fit(x, over.astype(np.int64)),
            "low": fit(x[~over], bucket[~over]),
            "high": fit(x[over], bucket[over] - 2)}

    def program_service(self):
        """The forests as the program's `PredictionService`."""
        from repro.core.forest import ObliviousForest
        from repro.core.predictor import PredictionService, TwoStageP95Model
        f = {k: ObliviousForest(v["feat_idx"], v["thresholds"],
                                v["leaf_values"], kind="rf", n_features=18)
             for k, v in self.service.items()}
        return PredictionService(f["criticality"], TwoStageP95Model(
            f["stage1"], f["low"], f["high"]))

    def program_table(self):
        """The subscription sums as the program's device table."""
        import jax.numpy as jnp
        from repro.serve.featurizer import SubscriptionTable
        s = self.sums
        return SubscriptionTable(*(jnp.asarray(a, jnp.float32) for a in (
            s.count, s.uf, s.lived7d, s.bucket, s.avg, s.p95)))
