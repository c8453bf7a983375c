"""Chassis x control steps of the fleet episodes completed in the
window, per second (host clock; each episode returns host arrays)."""


def read(ctx):
    w = ctx.win
    return w["chassis_steps"] / w["seconds"] if "chassis_steps" in w \
        else None
