"""Device seconds of the placement programs (the `place_batch` family,
serve/placement.py) in the traced part of the window, per micro-batch served there."""
from bench.trace_reduce import module_seconds


def read(ctx):
    if not ctx.trace or not ctx.trace.get("batches"):
        return None
    s = module_seconds(ctx.trace, r"place_batch")
    return None if s is None else s / ctx.trace["chips"] \
        / ctx.trace["batches"] * 1e3
