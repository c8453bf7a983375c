"""Arrivals decided (admitted or rejected) over the whole window, per
second of the window (host clock)."""


def read(ctx):
    w = ctx.win
    return w["decided"] / w["seconds"] if "decided" in w else None
