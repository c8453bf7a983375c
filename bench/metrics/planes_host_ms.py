"""Host milliseconds per micro-batch in the program's depart and cap
spans: the departure runs (their cap flush, host-to-device copies and
removal dispatch) and the power planes' dispatches and reads."""
from bench.spans import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, ["depart", "cap"])
