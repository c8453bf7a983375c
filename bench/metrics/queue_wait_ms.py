"""Mean, over the micro-batches of the window, of the program's queue
wait span: from the push of a batch's oldest arrival to the batch's
release for service (batch fill and watermark hold, not the load
generator's lateness)."""


def read(ctx):
    n, s = ctx.win.get("spans", {}).get("queue", (0, 0.0))
    return s / n * 1e3 if n else None
