"""Window milliseconds per micro-batch outside every program span: the
window's wall time less the program's total of outermost work spans
(`outermost`), per batch. What is left is the harness's own share
(generator, result taking, padding) and host code no span covers."""


def read(ctx):
    spans, batches = ctx.win.get("spans", {}), ctx.win.get("batches", 0)
    if not batches or "outermost" not in spans:
        return None
    return (ctx.win["seconds"] - spans["outermost"][1]) / batches * 1e3
