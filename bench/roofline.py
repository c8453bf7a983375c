"""Work of a kernel call counted from its shapes, and its roofline.

The forest kernel's work is the algorithm's, not the implementation's:
per row and per tree, `depth` threshold compares and one leaf read;
per call, the bytes of the rows, the thresholds and feature ids, the
leaf tables and the outputs, each read or written once. An
implementation that does more (today's one-hot matmuls) does not
raise the count, so the share reads the same work whatever runs it.
"""
from __future__ import annotations


def forest_work(rows: int, n_features: int, n_trees: int, depth: int,
                n_out: int, n_forests: int, itemsize: int = 4):
    """(operations, bytes) of scoring `rows` through `n_forests`
    oblivious forests."""
    ops = n_forests * rows * n_trees * (depth + n_out)
    nbytes = itemsize * (
        rows * n_features
        + n_forests * (2 * n_trees * depth
                       + n_trees * (1 << depth) * n_out
                       + rows * n_out))
    return ops, nbytes


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak_ops: float, peak_bytes: float):
    """(percent of the roofline, bound) for work that took `seconds`:
    the least time the chip could take over the time it took."""
    t_ops, t_bytes = ops / peak_ops, nbytes / peak_bytes
    bound = "memory" if t_bytes >= t_ops else "compute"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
