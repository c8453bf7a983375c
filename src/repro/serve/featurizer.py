"""Device-resident twin of `core/features.py` (serve-pipeline stage 1).

The offline featurizer walks python dicts of per-subscription
aggregates; at serving rates that walk *is* the latency budget. Here
the aggregates live as device arrays indexed by subscription id —
`SubscriptionTable` holds running *sums* (not means), so ingesting a
newly-labeled VM is one scatter-add and featurizing a whole arrival
micro-batch is one gather + a few elementwise ops, all inside a single
jit. Feature order matches `core.features.FEATURE_NAMES` exactly; the
parity test drives both paths with the same history.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import features as F
from repro.core.rounding import div
from repro.sim.telemetry import (
    VM_TYPES, ArrivalBatch, Population, arrival_batch)

N_FEATURES = len(F.FEATURE_NAMES)
N_VM_TYPES = len(VM_TYPES)

#: `core.features._DEFAULT_AGG` as a flat row for unseen subscriptions.
_DEFAULT_ROW = np.array(
    [F._DEFAULT_AGG["pct_uf"], F._DEFAULT_AGG["pct_7d"],
     F._DEFAULT_AGG["total"], *F._DEFAULT_AGG["bucket_mix"],
     F._DEFAULT_AGG["avg_avg"], F._DEFAULT_AGG["avg_p95"]], np.float32)


class SubscriptionTable(NamedTuple):
    """Running per-subscription sums (device arrays, capacity rows).

    Means are formed at featurize time, so an update is pure
    scatter-add and the table composes with jit/donation."""
    count: jnp.ndarray          # (N,) f32 — VMs observed
    uf_sum: jnp.ndarray         # (N,) f32 — sum of criticality labels
    lived7d_sum: jnp.ndarray    # (N,) f32 — sum of lifetime >= 168 h
    bucket_sum: jnp.ndarray     # (N, 4) f32 — P95-bucket histogram
    avg_util_sum: jnp.ndarray   # (N,) f32
    p95_util_sum: jnp.ndarray   # (N,) f32

    @property
    def capacity(self) -> int:
        return self.count.shape[0]


def empty_table(capacity: int) -> SubscriptionTable:
    """Fresh all-zero table with `capacity` subscription rows."""
    z = jnp.zeros(capacity, jnp.float32)
    return SubscriptionTable(z, z, z, jnp.zeros((capacity, 4), jnp.float32),
                             z, z)


def shard_table(table: SubscriptionTable, mesh,
                axis: str = "shard") -> SubscriptionTable:
    """Row-partition the table over a device mesh axis.

    Pads the capacity up to a multiple of the axis size and pins each
    row block to its device with a NamedSharding, so `update_table`
    scatter-adds and `featurize` gathers run distributed under jit —
    no featurizer code changes. The sharded serve pipeline applies
    this when a mesh is active (DESIGN.md §10).

    Capacity semantics of the padded window: because capacity is
    derived from the array shape, ids in [old capacity, padded
    capacity) become *valid* rows — `featurize` serves them the
    unseen-subscription defaults until ingested (they start all-zero),
    but `update_table` stores rather than drops them. Size the
    original capacity for your id space (as `from_history` does) and
    the window is never reached."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.shape[axis]
    cap = -(-table.capacity // n) * n

    def put(x):
        pad = [(0, cap - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
        spec = P(axis, *([None] * (x.ndim - 1)))
        return jax.device_put(jnp.pad(x, pad), NamedSharding(mesh, spec))

    return SubscriptionTable(*(put(a) for a in table))


def p95_bucket_jnp(p95_util: jnp.ndarray) -> jnp.ndarray:
    """jnp twin of `core.features.p95_bucket` (0-25/26-50/51-75/76-100).

    The host's `(x - 1e-9) // 25` epsilon pushes exact multiples of 25
    into the lower bucket, but 1e-9 underflows in float32 (eps at 25.0
    is ~3e-6). `ceil(x/25) - 1` encodes the same half-open-below
    boundary exactly — integer-percent inputs, the common telemetry
    case, are f32-representable and bucket identically to the f64
    host."""
    return jnp.clip(jnp.ceil(div(p95_util, 25.0, jnp)) - 1, 0,
                    F.N_UTIL_BUCKETS - 1).astype(jnp.int32)


@jax.jit
def update_table(table: SubscriptionTable, subscription: jnp.ndarray,
                 uf_label: jnp.ndarray, lifetime_hours: jnp.ndarray,
                 p95_util: jnp.ndarray,
                 avg_util: jnp.ndarray) -> SubscriptionTable:
    """Ingest a batch of labeled VMs (the daily label-bootstrap loop —
    paper §III-B — run incrementally). All args (B,); percent units.
    Ids outside [0, capacity) are dropped (XLA scatter semantics) —
    those subscriptions simply stay on the default-aggregates fallback
    that `featurize` serves for unseen ids."""
    sub = subscription.astype(jnp.int32)
    # out-of-range -> capacity: positive out-of-bounds scatter updates
    # are dropped (negative ones would wrap)
    sub = jnp.where((sub >= 0) & (sub < table.capacity), sub,
                    table.capacity)
    one = jnp.ones_like(uf_label, jnp.float32)
    bucket = jax.nn.one_hot(p95_bucket_jnp(p95_util), F.N_UTIL_BUCKETS,
                            dtype=jnp.float32)
    return SubscriptionTable(
        count=table.count.at[sub].add(one),
        uf_sum=table.uf_sum.at[sub].add(uf_label.astype(jnp.float32)),
        lived7d_sum=table.lived7d_sum.at[sub].add(
            (lifetime_hours >= 168).astype(jnp.float32)),
        bucket_sum=table.bucket_sum.at[sub].add(bucket),
        avg_util_sum=table.avg_util_sum.at[sub].add(avg_util),
        p95_util_sum=table.p95_util_sum.at[sub].add(p95_util))


def ingest_population(table: SubscriptionTable, history: Population,
                      uf_labels: np.ndarray) -> SubscriptionTable:
    """Fold a labeled population into the aggregates (one update)."""
    b = arrival_batch(history)
    avg = np.array([v.avg_util for v in history.vms], np.float32)
    return update_table(table, jnp.asarray(b.subscription),
                        jnp.asarray(np.asarray(uf_labels, np.float32)),
                        jnp.asarray(b.lifetime_hours),
                        jnp.asarray(b.p95_util), jnp.asarray(avg))


def table_from_history(history: Population, uf_labels: np.ndarray,
                       capacity: int) -> SubscriptionTable:
    """Bulk-load a table from an offline labeled history."""
    return ingest_population(empty_table(capacity), history, uf_labels)


@jax.jit
def featurize(table: SubscriptionTable, subscription: jnp.ndarray,
              cores: jnp.ndarray, memory_gb: jnp.ndarray,
              vm_type_idx: jnp.ndarray) -> jnp.ndarray:
    """(B,) arrival columns -> (B, N_FEATURES) f32, same layout as
    `core.features.build_features`. Unseen subscriptions — including
    ids outside [0, capacity), which XLA gathers would otherwise clamp
    onto the last row — fall back to the offline path's default
    aggregates."""
    sub = subscription.astype(jnp.int32)
    in_range = (sub >= 0) & (sub < table.capacity)
    sub = jnp.where(in_range, sub, 0)
    cnt = table.count[sub]                                   # (B,)
    seen = in_range & (cnt > 0)
    denom = jnp.maximum(cnt, 1.0)
    aggs = jnp.stack(
        [div(table.uf_sum[sub], denom, jnp),
         div(table.lived7d_sum[sub], denom, jnp),
         cnt], -1)                                           # (B, 3)
    bucket_mix = div(table.bucket_sum[sub], denom[:, None], jnp)  # (B, 4)
    util = jnp.stack([div(table.avg_util_sum[sub], denom, jnp),
                      div(table.p95_util_sum[sub], denom, jnp)],
                     -1)                                     # (B, 2)
    agg_row = jnp.concatenate([aggs, bucket_mix, util], -1)  # (B, 9)
    agg_row = jnp.where(seen[:, None], agg_row, _DEFAULT_ROW[None])
    onehot = jax.nn.one_hot(vm_type_idx, N_VM_TYPES, dtype=jnp.float32)
    return jnp.concatenate(
        [agg_row, cores[:, None].astype(jnp.float32),
         memory_gb[:, None].astype(jnp.float32), onehot], -1)


@partial(jax.jit, static_argnames=("pad_to",))
def _featurize_padded(table, subscription, cores, memory_gb, vm_type_idx,
                      pad_to):
    def pad(a):
        return jnp.pad(a, (0, pad_to - a.shape[0]))
    return featurize(table, pad(subscription), pad(cores), pad(memory_gb),
                     pad(vm_type_idx))


def featurize_batch(table: SubscriptionTable, batch: ArrivalBatch,
                    pad_to: int | None = None) -> jnp.ndarray:
    """Featurize one ingest micro-batch, optionally padded to a fixed
    batch size so the serving jit never re-specializes (padding rows
    use subscription 0 / type 0 and are dropped by the caller)."""
    args = (jnp.asarray(batch.subscription), jnp.asarray(batch.cores),
            jnp.asarray(batch.memory_gb), jnp.asarray(batch.vm_type_idx))
    if pad_to is None or pad_to == len(batch):
        return featurize(table, *args)
    return _featurize_padded(table, *args, pad_to=pad_to)
