"""Idle share of the device in the traced window
(`bench.trace_reduce.idle_percent`); it reads `device_idle.<cells>`
for every group of cells that `BENCHMARK.json` names."""
from bench.trace_reduce import idle_percent


def read(ctx):
    return idle_percent(ctx.trace)
