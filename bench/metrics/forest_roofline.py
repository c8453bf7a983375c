"""Share of the roofline the forest kernel reaches in the traced window:
the least time for the algorithm's work of every batch (bench/roofline.py,
compares against the bf16 peak, bytes against HBM bandwidth) over the
kernel's device time. None when the trace holds no forest kernel."""
from bench.roofline import forest_work, roofline_share
from bench.trace_reduce import op_seconds

N_FEATURES, N_FORESTS, N_OUT = 18, 4, 2
#: the Pallas forest kernel: the custom calls inside `served_query`
#: (the kernel has no name of its own in the trace yet)
KERNEL = r"^(served_query|forest)[\w.]*:custom-call$"


def read(ctx):
    if not ctx.trace or not ctx.trace.get("batches") or not ctx.peaks:
        return None
    s = op_seconds(ctx.trace, KERNEL)
    if not s:
        return None
    f = ctx.cfg["forest"]
    ops, nbytes = forest_work(ctx.cfg["batch_size"], N_FEATURES,
                              f["n_trees"], f["depth"], N_OUT, N_FORESTS)
    n = ctx.trace["batches"]
    share, _ = roofline_share(ops * n, nbytes * n, s / ctx.trace["chips"],
                              ctx.peaks["bf16_flops_per_s"],
                              ctx.peaks["hbm_bytes_per_s"])
    return share
