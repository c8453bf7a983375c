"""Device seconds of the jitted fleet engine (sim/fleet.py) per episode
in the traced window."""
from bench.trace_reduce import module_seconds


def read(ctx):
    if not ctx.trace or not ctx.trace.get("episodes"):
        return None
    s = module_seconds(ctx.trace, r"^jit_engine")
    return None if s is None else s / ctx.trace["chips"] \
        / ctx.trace["episodes"] * 1e3
