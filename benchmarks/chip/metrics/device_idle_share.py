"""Share of the traced window in which the device ran no op, in percent,
the mean over the cell's chips."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
