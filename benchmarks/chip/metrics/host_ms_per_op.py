"""Host milliseconds per op (api host edge): the traced window less the
time in which at least one of the cell's chips ran an op, over the
window's ops."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    return (t["window_s"] - t["busy_any_s"]) / ctx.ops * 1e3
