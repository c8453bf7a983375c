"""Host milliseconds per micro-batch in the program's record span: the
observability pillars' per-batch bookkeeping (registry, audit, windows,
quality, recorder)."""
from bench.spans import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, ["record"])
