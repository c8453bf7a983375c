"""Per-batch arithmetic of the readers of the program's host spans."""


def per_batch_ms(ctx, names):
    """Milliseconds per micro-batch of the window spent in the spans
    `names` (the program's `serve_span_seconds` totals, window deltas);
    None when the window served no batch or the program has no such
    span."""
    spans, batches = ctx.win.get("spans", {}), ctx.win.get("batches", 0)
    if not batches or not any(n in spans for n in names):
        return None
    return sum(spans.get(n, (0, 0.0))[1] for n in names) / batches * 1e3
