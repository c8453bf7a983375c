"""Host milliseconds per micro-batch in the program's featurize, infer
and place spans: the enqueue of the device programs, not their run."""
from bench.spans import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, ["featurize", "infer", "place"])
