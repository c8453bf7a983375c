"""Host reads of device arrays per micro-batch: the count of the
program's fetch spans (one `jax.device_get` each) over the batches of
the window."""


def read(ctx):
    spans, batches = ctx.win.get("spans", {}), ctx.win.get("batches", 0)
    if not batches or "fetch" not in spans:
        return None
    return spans["fetch"][0] / batches
