"""95th percentile of how late the load generator pushed a deployment
against its stamp (host clock): a starved generator shows here, not
as a fast system."""
import numpy as np


def read(ctx):
    lag = ctx.win.get("gen_lag_s")
    if lag is None or not len(lag) or not ctx.win.get("paced"):
        return None
    return float(np.percentile(lag, 95)) * 1e3
