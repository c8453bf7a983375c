"""Host milliseconds per micro-batch in the program's ingest and merge
spans (serve/ingest.py: the per-host queues and the watermark merge)."""
from bench.spans import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, ["ingest", "merge"])
