"""Device milliseconds of collective-permute ops per op (mesh rounds), the
mean over the cell's chips; nothing where the trace holds none."""


def read(ctx):
    t = ctx.trace
    if t is None or t["collective_s"] <= 0:
        return None
    return t["collective_s"] / ctx.ops * 1e3
