"""95th percentile of due-to-decision latency over every arrival of the
window: from the moment its deployment was due to be pushed to the
return of the call that gave its decision (host clock)."""
import numpy as np


def read(ctx):
    lat = ctx.win.get("latency_s")
    if lat is None or not len(lat) or not ctx.win.get("paced"):
        return None
    return float(np.percentile(lat, 95)) * 1e3
