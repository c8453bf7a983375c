"""Gathered departure removals per micro-batch: the count of the
program's remove spans (one `remove_batch` dispatch each) over the
batches of the window. None where the program has no such span."""


def read(ctx):
    spans, batches = ctx.win.get("spans", {}), ctx.win.get("batches", 0)
    if not batches or "remove" not in spans:
        return None
    return spans["remove"][0] / batches
