"""Degraded read's share of its HBM roofline, in percent: the least time
in which the chip can read the K survivor rows and write the lost rows
as uint32 at its peak HBM bandwidth, over the device-busy time per op."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    cfg = ctx.config
    rows = cfg["K"] + len(ctx.traffic["lost"])
    least_s = rows * cfg["W"] * 4 / ctx.peak["hbm_bytes_per_s"]
    return least_s / (t["busy_s"] / ctx.ops) * 100.0
