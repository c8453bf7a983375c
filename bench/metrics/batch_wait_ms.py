"""Mean, over the micro-batches of the window, of the time from the due
moment of a batch's first arrival to the return of the call that
decided the batch (host clock; batch fill plus service)."""
import numpy as np


def read(ctx):
    w = ctx.win.get("batch_wait_s")
    return None if w is None or not len(w) else float(np.mean(w)) * 1e3
