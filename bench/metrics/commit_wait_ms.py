"""Host milliseconds per micro-batch in the program's commit span: the
host waiting on the device for the batch's decisions."""
from bench.spans import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, ["commit"])
